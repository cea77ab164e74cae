"""Host time of the summarizer per window acked: the program's
``hist.summarize`` spans less their ``hist.summarize.wait`` children (the
host blocked on the summaries), in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    if after is None or "hist.summarize" not in after.spans:
        return None
    windows = run.work("ingest") // int(run.cell.config["values_per_window"])
    if windows == 0:
        return None
    return 1e3 * after.self_s("hist.summarize", "hist.summarize.wait") / windows
