"""Stream dispatch: share of the window in which the dispatch loop
waited for the feeder's next tape (harness ``wait`` spans), i.e. how
late the feeder ran.  Moves ``tasks_per_s``."""


def read(run):
    w = run["counts"]["window_s"]
    if w <= 0:
        return None
    return 100.0 * run["spans"].total("wait") / w
