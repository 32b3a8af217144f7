"""Multi-head latent attention: device milliseconds of the leaf
operations under the model's ``mla`` scope, per served request (the plan
and the fidelity forward), over one profiled cycle of the traffic's
pool after the window.  Moves ``tasks_per_s``."""


def read(run):
    c = run["counts"]
    s = c.get("scope_s", {}).get("mla")
    if not s or not c.get("scope_requests"):
        return None
    return s * 1e3 / c["scope_requests"]
