"""MoE layers: device milliseconds of the leaf operations under
``moe.route``, ``moe.experts`` and ``moe.shared``, per served request,
over one profiled cycle of the traffic's pool after the window.  Moves
``tasks_per_s``."""


def read(run):
    c = run["counts"]
    secs = c.get("scope_s", {})
    s = sum(v for k, v in secs.items() if k.startswith("moe."))
    if not s or not c.get("scope_requests"):
        return None
    return s * 1e3 / c["scope_requests"]
