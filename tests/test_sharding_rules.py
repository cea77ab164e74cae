"""Unit tests for the logical-axis sharding rules (no devices needed)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.sharding import Rules


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    # Rules only reads mesh.shape / axis_names — an abstract mesh suffices.
    return jax.sharding.AbstractMesh(shape, axes)


def test_train_rules_dense():
    cfg = get_config("qwen3-8b")
    r = Rules(cfg, fake_mesh(), "train", seq_len=4096)
    assert r(("vocab", "embed")) == P("model", "data")
    assert r(("embed", "mlp")) == P("data", "model")
    assert r(("layers", "embed", "heads", None)) == P(None, "data", "model", None)
    # kv=8 does not divide model=16 → replicated kv heads
    assert r(("embed", "kv_heads", None)) == P("data", None, None)
    assert r(("act_batch", "act_seq", None)) == P(("data",), "model", None)


def test_multi_pod_batch_axes():
    cfg = get_config("deepseek-7b")
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    r = Rules(cfg, mesh, "train", seq_len=4096)
    assert r(("act_batch", None)) == P(("pod", "data"), None)
    # weights replicate over pod (pure DP between pods)
    assert r(("embed", "mlp")) == P("data", "model")


def test_smollm_attention_replication_fallback():
    cfg = get_config("smollm-135m")
    r = Rules(cfg, fake_mesh(), "train", seq_len=4096)
    assert r(("embed", "heads", None)) == P("data", None, None)  # 9 !% 16
    assert r(("embed", "mlp")) == P("data", "model")  # 1536 % 16 == 0
    assert any("heads" in d for d in r.degradations())


def test_decode_kv_seq_sharding():
    cfg = get_config("qwen3-8b")
    r = Rules(cfg, fake_mesh(), "decode", seq_len=32768)
    assert r(("batch_kv", "kv_seq", "kv_heads_cache", None)) == P(
        ("data",), ("model",), None, None
    )
    # decode: no sequence parallelism on the (length-1) activation seq
    assert r(("act_batch", "act_seq", None)) == P(("data",), None, None)


def test_long_context_rules():
    cfg = get_config("jamba-v0.1-52b")
    r = Rules(cfg, fake_mesh(), "decode_long", seq_len=524288)
    # batch=1 → replicated; KV sequence spreads over data AND model
    assert r(("batch_kv", "kv_seq", "kv_heads_cache", None)) == P(
        None, ("data", "model"), None, None
    )


def test_prefill_kv_seq_now_sharded():
    """§Perf P2: prefill caches must not materialize unsharded."""
    cfg = get_config("deepseek-7b")
    r = Rules(cfg, fake_mesh(), "prefill", seq_len=32768)
    spec = r(("batch_kv", "kv_seq", "kv_heads_cache", None))
    assert spec[1] in ("model", ("model",))  # P() normalizes 1-tuples


def test_expert_sharding():
    for arch, divisible in [("dbrx-132b", True), ("llama4-maverick-400b-a17b", True)]:
        cfg = get_config(arch)
        r = Rules(cfg, fake_mesh(), "train", seq_len=4096)
        spec = r(("experts", "embed", "expert_mlp"))
        assert spec == P("model", "data", None)


def test_seq_parallel_divisibility_guard():
    cfg = get_config("qwen3-8b")
    r = Rules(cfg, fake_mesh(), "train", seq_len=100)  # 100 !% 16
    assert r(("act_batch", "act_seq", None)) == P(("data",), None, None)


def test_vocab_padding_whisper():
    cfg = get_config("whisper-medium")
    assert cfg.vocab_size % 16 == 0  # padded 51865 → 51872
    r = Rules(cfg, fake_mesh(), "train", seq_len=4096)
    assert r(("vocab", "embed")) == P("model", "data")
