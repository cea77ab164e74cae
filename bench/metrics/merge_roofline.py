"""Share of the HBM-bandwidth roofline reached by the query merge
(``jit_merge_stacks``).  A bytes bound: the least bytes the merge of a panel
must move are its canonical nodes' summaries read once (``k`` rows of T+1
boundaries and T sizes) and its β+1 boundaries and β sizes written, in
float32; the roofline time is those bytes over the chip's peak bandwidth.
Counted over the distinct panels of every batch, which in a read-only cell
with no answer-cache hits are every merge of the window."""

PROGRAM = "jit_merge_stacks"


def merge_bytes(cover_nodes: int, panels: int, T: int, beta: int) -> int:
    """Least bytes moved merging ``panels`` panels of ``cover_nodes`` nodes in all."""
    return 4 * (cover_nodes * (2 * T + 1) + panels * (2 * beta + 1))


def read(run, before, after):
    distinct = sum(s.distinct for s in run.clients("query"))
    if run.trace is None or not distinct:
        return None
    if after["hits"] != before["hits"]:
        return None  # an answer-cache hit merges nothing: the count would be too high
    secs = run.trace.programs.get(PROGRAM, 0.0)
    if secs <= 0:
        return None
    cfg = run.cell.config
    nodes = sum(s.cover_nodes for s in run.clients("query"))
    least = merge_bytes(nodes, distinct, int(cfg["T"]), int(cfg["beta"]))
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / secs


def snapshot(svc):
    return svc.registry.cache_stats()
