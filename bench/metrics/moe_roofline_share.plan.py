"""Held experts' grouped matmuls: share of their TPU v5e roofline.  The
least time the chip could take, the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bytes/s (``bench/flops.expert_cost``: the
token-expert pairs the engine's ``moe.routed_pairs`` counter saw, 6 d F
FLOPs each; every held expert's weights read once per MoE layer
execution, plus the pairs' activation rows), over the device time under
``moe.experts``, in one profiled cycle of the pool.  Moves
``tasks_per_s``."""


def read(run):
    c = run["counts"]
    t = c.get("scope_s", {}).get("moe.experts")
    if not t or not c.get("peak_flops") or "expert_cost" not in c:
        return None
    fl, by = c["expert_cost"]
    return 100.0 * max(fl / c["peak_flops"], by / c["peak_bw"]) / t
