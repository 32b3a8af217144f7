"""Host feeder: milliseconds the feeder thread spent in
``StreamFeeder.next_chunk`` per simulated interval it built, over the
window (harness ``feed`` spans).  Moves ``tasks_per_s``."""


def read(run):
    spans, c = run["spans"], run["counts"]
    n = spans.count("feed")
    if not n:
        return None
    return spans.total("feed") * 1e3 / (n * c["chunk_intervals"])
