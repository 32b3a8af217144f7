"""The simulator's device programs compile for a described TPU v5e.

Nothing here runs on a chip: each program is lowered against a
``v5e:2x2`` topology that JAX describes without one, compiled by the
TPU compiler, and its memory analysis checked against one chip's
16 GiB of HBM.  Covered at the serving size (K=512 ring slots, 64
intervals per chunk, the 50-worker Table-3 fleet):

  * the streaming chunk program for the static ``mc`` engine and for
    SplitPlace (MAB decider + DASO placer at the host
    ``SurrogatePlacer`` sizes), on one described chip, with every phase
    scope of the interval body in its operations' metadata, and the
    DASO ascent's float64 products as int8 dots on the MXU;
  * the sharded grid program (``shard_map`` over a 1-D ``"grid"`` mesh)
    on four described chips, for an 8-cell (seed x λ) grid;
  * the plan engine's three programs for the Kimi-K2 cut at every request
    length of the plan cell's pool, MLA's attention in each as the
    causal flash kernel under the ``mla`` scope.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import json
import os

import numpy as np
import pytest

HBM_BYTES = 16 * 2**30
K, T, LAM = 512, 64, 6.0
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "workloads",
                       "kimi-k2-ep32.splitplace.plan.json")) as _f:
    PLAN_TRAFFIC = json.load(_f)
POOL_LENGTHS = sorted(set(PLAN_TRAFFIC["pool"]["lengths"]))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


def _specs(tree, sharding_of):
    """ShapeDtypeStructs of a host/CPU pytree, each leaf placed by
    ``sharding_of(leaf)``."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding_of(x)), tree)


def _fits(compiled, what):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, (what, total)
    return mem


@pytest.fixture(scope="module", params=["mc", "splitplace"])
def stream_compiled(request, one_chip):
    """(policy, the stream chunk program compiled for one described
    v5e chip), one compile per engine for every test that reads it."""
    import jax
    import jax.numpy as jnp

    from repro.env.cluster import make_cluster
    from repro.env.jaxsim import driver, kernels, stream
    from repro.env.jaxsim.arrays import ClusterArrays
    from repro.launch.experiments import seeded_surrogate
    policy = request.param
    cluster = make_cluster()
    theta, cfg = seeded_surrogate(cluster.n, seed=0)
    engine, es0, feeder_kw = stream.make_stream_policy(
        policy, cluster=cluster, daso_theta=theta, daso_cfg=cfg)
    if policy == "splitplace":
        assert engine.daso_cfg == cfg           # the DASO placer is in
    feeder = stream.StreamFeeder(lam=LAM, seed=0, cluster=cluster,
                                 **feeder_kw)
    tape = feeder.next_chunk(T)
    frag = tape["vinstr" if "vinstr" in tape else "instr"]
    with jax.enable_x64(True):
        cld = ClusterArrays.from_cluster(cluster).as_dict()
        carry = (kernels.init_state(K, frag.shape[-1], cluster.n),
                 driver._init_acc(cluster.n),
                 jax.tree_util.tree_map(jnp.asarray, es0))
        key = driver._static_key(engine, tape, K, cluster.n, feeder.substeps,
                                 feeder.interval_s, 0.5, "xla", "stream")
        prog = jax.jit(driver._stream_program(*key[:-1]), donate_argnums=(2,))
        on_chip = lambda _: one_chip
        compiled = prog.lower(_specs(tape, on_chip), _specs(cld, on_chip),
                              _specs(carry, on_chip),
                              jax.ShapeDtypeStruct((), jnp.int64,
                                                   sharding=one_chip)
                              ).compile()
    return policy, compiled


def test_stream_chunk_compiles_for_v5e(stream_compiled):
    policy, compiled = stream_compiled
    _fits(compiled, policy)
    assert "f64[" in compiled.as_text()         # the physics stays float64


def test_stream_chunk_carries_phase_scopes(stream_compiled):
    """Every phase scope of the interval body survives the TPU
    compiler into the operations' ``op_name`` metadata, inside the
    chunk loop's body, where a device profile reads it."""
    import re

    from repro.env.jaxsim import driver
    policy, compiled = stream_compiled
    bodies = {"/" + n.split("/while/body/", 1)[1]
              for n in re.findall(r'op_name="([^"]*)"', compiled.as_text())
              if "/while/body/" in n}
    for phase in driver.PHASES:
        assert any(f"/{phase}/" in b for b in bodies), (policy, phase)


def _computations(text):
    """{computation name: its instruction lines} of an HLO module."""
    import re
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _closure(comps, root):
    """``root`` and every computation it calls, transitively."""
    import re
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)",
                               line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def test_stream_ascent_dots_run_on_the_mxu(stream_compiled):
    """SplitPlace's DASO ascent runs its float64 surrogate products as
    int8 x int8 -> int32 dots (``core/sliced_dot``) under the ``place``
    scope of the chunk loop's body.  Inside the ascent's loop every
    array as large as the first-layer weight is int8, the weight's
    slices as read: no float copy of it, no per-step limb split or cut
    (no ``X64SplitHigh`` of it).  ``mc``, which runs no ascent, has no
    integer dot."""
    import math
    import re

    from repro.core import daso
    from repro.launch.experiments import seeded_surrogate
    policy, compiled = stream_compiled
    text = compiled.as_text()
    comps = _computations(text)
    types = dict(re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ", text,
                            re.M))
    int_dot = re.compile(r"= s32\[[^\]]*\]\S* (?:convolution|dot)\(([^)]*)\)")

    def int_dots(names):
        return [line for c in names for line in comps[c]
                if (m := int_dot.search(line)) and all(
                    types.get(a.strip().lstrip("%"), "").startswith("s8[")
                    for a in m.group(1).split(","))]

    if policy == "mc":
        assert not int_dots(comps)
        return
    loops = [_closure(comps, body) for lines in comps.values()
             for line in lines
             for body in re.findall(r" while\(.*body=%?([\w.\-]+)", line)]
    ascent = min((c for c in loops if int_dots(c)), key=len)
    assert any("/while/body/" in d and "/place/" in d
               for d in int_dots(ascent))
    _, cfg = seeded_surrogate(50)
    w0 = daso.feature_size(cfg) * cfg.hidden              # W0 is K x H
    large = []
    for line in (line for c in ascent for line in comps[c]):
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ", line)
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                      m.group(2) if m else ""):
            size = math.prod(int(d) for d in dims.split(",") if d)
            if size >= w0 and dtype != "s8":
                large.append(line[:160])
    assert not large, large[:5]


def test_sharded_grid_compiles_for_v5e_2x2(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.env.cluster import make_cluster
    from repro.env.jaxsim import (compile_trace, default_capacity, driver,
                                  engines, make_static_decider, stack_traces)
    from repro.env.jaxsim.arrays import ClusterArrays
    cluster = make_cluster()
    dec = make_static_decider("mc")
    traces = [compile_trace(dec, lam=lam, seed=s, n_intervals=100)
              for lam in (6.0, 12.0) for s in range(4)]
    mesh = Mesh(np.asarray(topo.devices[:4]), ("grid",))
    engine = engines.StaticEngine()
    with jax.enable_x64(True):
        leaves = stack_traces(traces)
        cld = ClusterArrays.from_cluster(cluster).as_dict()
        key = driver._static_key(engine, leaves, default_capacity(traces),
                                 cluster.n, traces[0].substeps,
                                 traces[0].interval_s, 0.5, "xla")
        prog = driver._sharded_program(key, mesh, donate=True)
        compiled = prog.lower(
            _specs(leaves, lambda _: NamedSharding(mesh, P("grid"))),
            _specs(cld, lambda _: NamedSharding(mesh, P())), ()).compile()
    _fits(compiled, "sharded grid")
    out = compiled.output_shardings
    metrics = out["metrics"]
    assert metrics.spec == P("grid") and len(metrics.device_set) == 4


# ------------------------------------------------------- the plan engine

PLAN_HBM = 15.5e9


@pytest.fixture(scope="module")
def plan_setup(one_chip):
    """(the configuration file, the cut's params by shape, L -> {program:
    compiled}): the plan engine's three programs for the Kimi-K2 cut
    (``bench/configs/kimi-k2-ep32.json``, published widths) compiled for
    one described v5e chip at a request of the traffic's batch and
    length L, with the cell's probe positions."""
    import sys

    import jax
    import jax.numpy as jnp
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench.paths.plan import program_config
    from repro.models import init_params
    from repro.serving.engine import SplitPlaceEngine
    with open(os.path.join(ROOT, "bench", "configs",
                           "kimi-k2-ep32.json")) as f:
        cfg = json.load(f)
    tr = PLAN_TRAFFIC
    mcfg = program_config(cfg)
    shapes = jax.eval_shape(lambda k: init_params(k, mcfg),
                            jax.random.PRNGKey(0))
    on_chip = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    eng = SplitPlaceEngine(shapes, mcfg, num_stages=tr["stages"],
                           num_branches=tr["branches"])
    progs = {"layer_plan": eng._pipe, "semantic_plan": eng._branch,
             "monolithic": eng._mono}

    def compile_at(L):
        b = tr["batch"][str(L)]
        batch = {"tokens": jax.ShapeDtypeStruct((b, L), jnp.int32,
                                                sharding=one_chip),
                 "probe": jax.ShapeDtypeStruct((tr["compare"]["positions"],),
                                               jnp.int32, sharding=one_chip)}
        return {name: fn.lower(on_chip, batch).compile()
                for name, fn in progs.items()}
    return cfg, shapes, compile_at


@pytest.fixture(scope="module")
def plan_compiled(plan_setup):
    """(the configuration file, the cut's params by shape, {program:
    compiled}) at the traffic's largest request."""
    cfg, shapes, compile_at = plan_setup
    return cfg, shapes, compile_at(max(POOL_LENGTHS))


@pytest.fixture(scope="module", params=POOL_LENGTHS)
def plan_at_length(request, plan_setup, plan_compiled):
    """(L, {program: compiled}) at each request length of the pool."""
    L = request.param
    if L == max(POOL_LENGTHS):
        return L, plan_compiled[2]
    return L, plan_setup[2](L)


@pytest.mark.parametrize("program", ["layer_plan", "semantic_plan",
                                     "monolithic"])
def test_plan_program_fits_one_v5e(plan_compiled, program):
    """Each plan program at published widths and the largest request
    fits under 15.5 GB with its 7 GB of weights."""
    _, _, compiled = plan_compiled
    mem = compiled[program].memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 7e9 < mem.argument_size_in_bytes and total < PLAN_HBM, total


def test_layer_plan_is_the_monolithic_program(plan_compiled):
    """The layer plan and the fidelity forward compile to one program
    (names and source metadata aside), so that they agree bit for bit
    on the chip: the same batch, probe included, reaches both."""
    _, _, compiled = plan_compiled
    assert _program_body(compiled["layer_plan"].as_text()) \
        == _program_body(compiled["monolithic"].as_text())


def _program_body(text):
    """A compiled program's HLO without its names and source metadata."""
    import re
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r",? ?stack_frame_id=\d+", "", text)
    text = re.sub(r"^HloModule \S+", "HloModule", text, flags=re.M)
    return [line for line in text.splitlines()
            if not re.match(r'^\s*\d+ ["{]', line)]


@pytest.mark.parametrize("program", ["layer_plan", "semantic_plan",
                                     "monolithic"])
def test_plan_mla_runs_the_fused_kernel(plan_compiled, plan_at_length,
                                        program):
    """At every request length of the pool, each plan program runs MLA's
    attention as the causal flash kernel, one Mosaic call per MLA layer
    (the semantic plan's branches vmapped into it), each under the
    ``mla`` scope that ``mla_ms_per_request.plan`` reads (``vmap(mla)``
    in the semantic plan, as all its MLA operations are); no float32
    (..., 512, 1024) score block of the blockwise path and no loop under
    ``mla`` is left; and the program fits one chip."""
    import re

    from bench import trace_phases
    from bench.paths.plan import SCOPES
    L, compiled = plan_at_length
    text = compiled[program].as_text()
    scopes = {op: scope for (_, op), scope
              in trace_phases.op_scopes(text).items()}
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom-call\(.*"
                         r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*causal_flash', text, re.M)
    assert len(kernels) == plan_compiled[0]["num_hidden_layers"], \
        (L, program, kernels)
    scope = "vmap(mla)" if program == "semantic_plan" else "mla"
    assert all(trace_phases.phase_of(scopes[k], SCOPES + (scope,)) == scope
               for k in kernels), [scopes[k] for k in kernels]
    assert not re.search(r"f32\[[\d,]*512,1024\]", text)
    assert not [n for n in re.findall(r' while\(.*op_name="([^"]*)"', text)
                if "/mla/" in n]
    mem = compiled[program].memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < PLAN_HBM, (L, program, total)


def test_layer_plan_is_the_monolithic_program_at_each_length(plan_at_length):
    """The layer plan and the fidelity forward are one program at every
    request length of the pool."""
    _, compiled = plan_at_length
    assert _program_body(compiled["layer_plan"].as_text()) \
        == _program_body(compiled["monolithic"].as_text())


def test_semantic_plan_copies_no_weight(plan_compiled):
    """The branches read their head and channel slices in place: outside
    the fused computations (whose operands are not buffers of their own)
    and the loops' pass-through, the semantic program materialises no
    array with the size of a weight that is at least half a layer's
    held-expert stack, whole or one branch's slice of it.  (Activations
    larger than that stack exist: 2 branches x 16384 tokens x 7168.)"""
    import math
    import re

    cfg, shapes, compiled = plan_compiled
    import jax
    stack = cfg["n_routed_experts"] * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]
    B = 2
    big = {math.prod(a.shape) for a in jax.tree.leaves(shapes)
           if math.prod(a.shape) * B >= stack}
    forbidden = big | {n // B for n in big}
    text = compiled["semantic_plan"].as_text()
    comps = _computations(text)
    fused = {c for lines in comps.values() for line in lines if " fusion(" in line
             for c in re.findall(r"calls=%?([\w.\-]+)", line)}
    skip = ("parameter", "get-tuple-element", "bitcast", "tuple", "while",
            "conditional", "copy-start", "copy-done")
    copies = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(",
                         line)
            if not m or m.group(2) in skip:
                continue
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)):
                n = math.prod(int(d) for d in dims.split(",") if d)
                if n in forbidden:
                    copies.append(line.strip()[:160])
    assert not copies, copies[:5]
