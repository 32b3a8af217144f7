"""CPU rehearsal of the plan cell (``kimi-k2-ep32.splitplace.plan``) at a
tiny size through the harness's internal entry, and the faults the cell
must catch, planted under the timed path: each must turn ``correct``
false.  The rehearsal computes in float32 (the chip computes in bf16
against the same limits).  Nothing here is a device number."""
import functools

import jax.numpy as jnp
import pytest

from bench import run, trace_phases
from bench.paths import plan

CELL = "kimi-k2-ep32.splitplace.plan"
SEED = 2**31 + 77
TINY = {"config": {"hidden_size": 64, "num_attention_heads": 4,
                   "num_key_value_heads": 4, "q_lora_rank": 32,
                   "kv_lora_rank": 16, "qk_nope_head_dim": 16,
                   "qk_rope_head_dim": 8, "v_head_dim": 16,
                   "intermediate_size": 128, "moe_intermediate_size": 32,
                   "router_experts": 16, "n_routed_experts": 8,
                   "experts_held": [4, 8], "num_experts_per_tok": 4,
                   "vocab_size": 256, "num_hidden_layers": 3,
                   "param_dtype": "float32", "compute_dtype": "float32"},
        "traffic": {"pool": {"lengths": [32, 32, 64, 64],
                             "tight": [True, False, True, False]},
                    "batch": {"32": 2, "64": 2}, "latency_runs": 1,
                    "compare": {"positions": 32, "window_per_pair": 1,
                                "route_eps": 0.1},
                    "trace_seconds": 0.2}}


def _run(trace=0, seconds=0.3):
    return run.run_cell(CELL, SEED, seconds, trace, allow_cpu=True,
                        overrides=TINY)


def test_plan_rehearsal():
    result, checks = _run()
    assert result["correct"], checks
    assert set(result["metrics"]) == {"tasks_per_s", "chunk_p95_ms",
                                      "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert checks["plans_missing"]["value"] == 0
    assert checks["layer_plan_vs_forward"]["value"] == 0.0


def test_plan_scopes_on_a_cpu_trace(monkeypatch):
    """A traced rehearsal, its scope pass pointed at the CPU's operation
    lines: every model scope reads device time, and the held experts'
    pairs are counted."""
    xla = lambda ln: ln.startswith("tf_XLA")
    monkeypatch.setattr(trace_phases, "load", functools.partial(
        trace_phases.load, is_device=lambda p: p == "/host:CPU",
        is_busy=xla, is_ops=xla))
    seen = {}
    orig = plan.scope_profile

    def spy(*a, **k):
        seen.update(orig(*a, **k))
        return seen
    monkeypatch.setattr(plan, "scope_profile", spy)
    result, checks = _run(trace=1)
    assert result["correct"], checks
    assert set(seen["scope_s"]) == set(plan.SCOPES)
    assert seen["scope_requests"] == 4 and seen["routed_pairs"] > 0
    assert seen["scope_compiles"] == 0
    # the CPU has no device plane and no published peaks: the traced
    # metrics that need them stay out of the line
    assert "mfu.plan" not in result["metrics"]


def _float8_experts(moe, monkeypatch):
    """The held experts' weights stored as float8 e4m3 in the program."""
    orig = plan.load_params

    def f8(*a, **k):
        params = orig(*a, **k)
        for blk in params["prefix"]:
            if "moe" in blk:
                blk["moe"].update({n: blk["moe"][n].astype(jnp.float8_e4m3fn)
                                   for n in ("w_gate", "w_up", "w_down")})
        return params
    monkeypatch.setattr(plan, "load_params", f8)


def _softmax_scores(moe, monkeypatch):
    import dataclasses
    orig = moe.router_topk
    monkeypatch.setattr(moe, "router_topk", lambda p, x, m: orig(
        p, x, dataclasses.replace(m, scoring_func="softmax")))


def _shared_dropped(moe, monkeypatch):
    monkeypatch.setattr(moe, "shared_expert",
                        lambda p, x, cfg: jnp.zeros_like(x))


@pytest.mark.parametrize("fault", ["float8_experts", "softmax_scores",
                                   "shared_dropped"])
def test_plan_fault_is_caught(fault, monkeypatch):
    from repro.models import moe
    {"float8_experts": _float8_experts, "softmax_scores": _softmax_scores,
     "shared_dropped": _shared_dropped}[fault](moe, monkeypatch)
    result, checks = _run()
    assert not result["correct"], checks
