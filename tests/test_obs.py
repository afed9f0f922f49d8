"""Unified telemetry (repro.obs): device-side wire/drop/shadow counters on
the MoE metrics pytree, host spans on the profiler's clock, the train
step's named scopes, and the pluggable metrics sinks.

The wire counters' contract is strong: for every distributed schedule
(serial a2a, ppermute-decomposed, bf16 wire, ragged/dropless) the counter
must equal BOTH the hand-computed exchange size AND the optimized HLO's
collective output bytes (roofline.collective_bytes) — and turning the
counters off (DistConfig.obs=False) must leave the program's collectives
byte-for-byte unchanged, i.e. telemetry is free.
"""
import glob
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dist_utils as du
from repro.core.monitor import LoadMonitor
from repro.obs import sink as obs_sink
from repro.obs import trace as obs_trace
from repro.obs.counters import ObsCounters
from repro.obs.stats import StepStats, modeled_collective_bytes


# ---------------------------------------------------------------------------
# Counters: single-device semantics + pytree accumulation
# ---------------------------------------------------------------------------


def test_local_counters_single_device():
    """No dist: nothing crosses any wire; dropped = drop_frac * (T * k)."""
    env = du.moe_env(capacity_factor=0.5)  # force capacity overflow
    y, m = du.oracle(env)
    T = env.x.shape[0] * env.x.shape[1]
    assert float(m.obs.wire_elems) == 0.0
    assert float(m.obs.wire_bytes) == 0.0
    assert float(m.obs.shadow_hits) == 0.0
    assert float(m.obs.imbalance) == 1.0
    assert float(m.drop_frac) > 0.0
    np.testing.assert_allclose(float(m.obs.dropped),
                               float(m.drop_frac) * T * env.cfg.top_k,
                               rtol=1e-5)


def test_counters_accumulate_like_metrics():
    """ObsCounters is '+'-accumulable (the layer scan sums it)."""
    a = ObsCounters(*(jnp.float32(v) for v in (1, 2, 3, 4, 1.5, 0.25, 0.75)))
    b = ObsCounters(*(jnp.float32(v) for v in (10, 20, 30, 40, 0.5, 1, 19)))
    s = a + b
    assert [float(v) for v in s] == [11, 22, 33, 44, 2.0, 1.25, 19.75]
    z = ObsCounters.zero()
    assert [float(v) for v in (z + a)] == [float(v) for v in a]
    d = a.as_dict()
    assert set(d) == {"wire_elems", "wire_bytes", "dropped", "shadow_hits",
                      "imbalance", "wire_bytes_intra", "wire_bytes_inter"}


# ---------------------------------------------------------------------------
# Multi-rank wire counters: hand math == device counter == optimized HLO
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_wire_counters_match_hand_math_and_hlo():
    out = du.run("""
    import numpy as np, jax, jax.numpy as jnp
    import dist_utils as du
    from repro.core import fmoe
    from repro.core.dispatch import expert_capacity
    from repro.launch.roofline import collective_bytes

    E, k, d = 8, 2, 32
    mesh = du.make_mesh(2, 4)  # tokens over 8 ranks, experts over mp=4
    axes = ("data", "model")
    mp, shards = 4, 8
    env = du.moe_env()          # T=128, capacity_factor=8 (no drops)
    t = 128 // shards
    C = expert_capacity(t, E, k, env.cfg.capacity_factor)

    def run(env, dist, params=None):
        with mesh:
            fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, env.cfg,
                                                      dist=dist))
            p = env.params if params is None else params
            y, m = fn(p, env.x)
            # lower the FULL (y, m) program so the counts leg isn't DCE'd
            txt = fn.lower(p, env.x).compile().as_text()
        cb = collective_bytes(txt)
        return m, float(cb.get("all-to-all", 0)
                        + cb.get("collective-permute", 0))

    # serial capacity a2a, f32 wire: full (E, C, d) payload each way + the
    # int32 Fig-2 counts exchange
    m, hlo = run(env, fmoe.DistConfig(mesh, axes))
    elems = E * C * d * 2 + E
    assert float(m.obs.wire_elems) == elems, (float(m.obs.wire_elems), elems)
    assert float(m.obs.wire_bytes) == 4 * elems
    assert float(m.obs.wire_bytes) == hlo, (float(m.obs.wire_bytes), hlo)
    assert float(m.obs.dropped) == 0.0
    assert float(m.obs.shadow_hits) == 0.0
    assert float(m.obs.imbalance) >= 1.0
    # flat (single-level) exchange: the split counters attribute every
    # byte to the inter-node share (tests/test_hier_a2a.py locks the
    # two-level split)
    assert float(m.obs.wire_bytes_intra) == 0.0
    assert float(m.obs.wire_bytes_inter) == float(m.obs.wire_bytes)

    # bf16 wire: payload bytes halve, counts leg stays int32
    m, hlo = run(env, fmoe.DistConfig(mesh, axes, wire_dtype="bf16"))
    b = E * C * d * 2 * 2 + E * 4
    assert float(m.obs.wire_bytes) == b, (float(m.obs.wire_bytes), b)
    assert float(m.obs.wire_bytes) == hlo, (float(m.obs.wire_bytes), hlo)

    # ppermute-decomposed pipeline: a rank's own slice never moves, so only
    # (mp-1)/mp of every leg (payloads AND counts) crosses the wire
    m, hlo = run(env, fmoe.DistConfig(mesh, axes, overlap_chunks=2))
    b = 0.75 * 4 * (E * C * d * 2 + E)
    assert float(m.obs.wire_bytes) == b, (float(m.obs.wire_bytes), b)
    assert float(m.obs.wire_bytes) == hlo, (float(m.obs.wire_bytes), hlo)

    # ragged (dropless): pad-to-max-per-peer shards, B = t*k rows per peer
    env_r = du.moe_env(dispatch="ragged")
    B = t * k
    m, hlo = run(env_r, fmoe.DistConfig(mesh, axes))
    elems = mp * B * d * 2 + E
    assert float(m.obs.wire_elems) == elems, (float(m.obs.wire_elems), elems)
    assert float(m.obs.wire_bytes) == 4 * elems
    assert float(m.obs.wire_bytes) == hlo, (float(m.obs.wire_bytes), hlo)
    assert float(m.obs.dropped) == 0.0

    # shadowed hot experts: skewed router sends every assignment to the two
    # shadowed experts -> shadow_hits counts ALL global (token, slot) pairs
    from repro.placement import from_logical
    envh = du.skew_router(du.moe_env())
    pl = du.hot_shadow_plan(np.array([10, 5, 3, 3, 2, 2, 1, 1], float), 4, 4)
    m, hlo = run(envh, fmoe.DistConfig(mesh, axes, placement=pl),
                 params=from_logical(envh.params, pl))
    assert float(m.obs.shadow_hits) == 128 * k, float(m.obs.shadow_hits)
    assert float(m.obs.dropped) == 0.0

    # psum (decode) mode: tokens sharded over data only -> one (t, d)
    # all-reduce is the entire wire traffic (no counts leg)
    m, _ = run(env, fmoe.DistConfig(mesh, ("data",)))
    t_ps = 128 // 2
    assert float(m.obs.wire_elems) == t_ps * d, float(m.obs.wire_elems)
    assert float(m.obs.wire_bytes) == t_ps * d * 4
    assert float(m.obs.imbalance) >= 1.0
    print("wire counters ok")
    """, devices=8)
    assert "wire counters ok" in out


@pytest.mark.tier1
def test_obs_off_leaves_collectives_byte_identical():
    """DistConfig.obs gates the counters; the HLO regression locking in
    'telemetry is free': obs=True vs obs=False programs have identical
    collective ops, byte for byte."""
    out = du.run("""
    import re
    import jax
    import dist_utils as du
    from repro.core import fmoe
    from repro.launch.roofline import collective_bytes

    # op DEFINITIONS only (result names recur as operand references, so a
    # raw substring count is meaningless); -start counted once, -done not
    OPRE = re.compile(r"=\\s*[^=]*?(all-reduce|all-gather|reduce-scatter"
                      r"|all-to-all|collective-permute)(-start)?\\(")

    def op_counts(txt):
        c = {}
        for m in OPRE.finditer(txt):
            c[m.group(1)] = c.get(m.group(1), 0) + 1
        return c

    mesh = du.make_mesh(1, 4)
    for dispatch, kw in (("capacity", {}), ("capacity",
                         dict(overlap_chunks=2, wire_dtype="bf16")),
                        ("ragged", {})):
        env = du.moe_env(dispatch=dispatch)
        txts = {}
        for obs in (True, False):
            dist = fmoe.DistConfig(mesh, ("data", "model"), obs=obs, **kw)
            with mesh:
                fn = jax.jit(lambda p, x: fmoe.fmoe_apply(p, x, env.cfg,
                                                          dist=dist))
                txts[obs] = fn.lower(env.params, env.x).compile().as_text()
        cb_on, cb_off = (collective_bytes(txts[o]) for o in (True, False))
        assert cb_on == cb_off, (dispatch, kw, cb_on, cb_off)
        assert op_counts(txts[True]) == op_counts(txts[False]), (
            dispatch, kw, op_counts(txts[True]), op_counts(txts[False]))
    print("obs off identical")
    """, devices=4)
    assert "obs off identical" in out


# ---------------------------------------------------------------------------
# Named scopes of the train step (repro.obs.scopes)
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_scopes_leave_optimized_hlo_unchanged():
    """The scopes are metadata only: the tiny 1x4 expert-parallel train step
    (the _moe_a2a path) compiled with every scope and with the helper
    swapped for a nullcontext has the same collective set, byte for byte,
    once metadata is stripped; indeed the same instructions throughout,
    up to XLA's numbering of their names."""
    out = du.run("""
    import contextlib
    import re
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import jit_train_step, moe_dist
    from repro.models import lm
    from repro.obs import scopes
    from repro.optim.adamw import AdamW

    cfg = reduced(get_config("fastmoe-gpt"), num_layers=2, d_model=64)
    mesh = make_local_mesh(1, 4)
    assert moe_dist(cfg, mesh, 8 * 32).mode == "a2a"
    opt = AdamW()

    def optimized():
        step, _, _ = jit_train_step(cfg, opt, mesh, 8, 32)
        p = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                  cfg))
        return step.lower(p, jax.eval_shape(opt.init, p),
                          {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)},
                          jax.ShapeDtypeStruct((), jnp.int32)
                          ).compile().as_text()

    on = optimized()
    scopes.scope = contextlib.nullcontext
    off = optimized()
    assert set(re.findall(r"fmoe[.][a-z_]+", on)) >= set(scopes.ALL)
    assert not set(re.findall(r"fmoe[.][a-z_]+", off)) & set(scopes.ALL)

    def instructions(txt):
        # every instruction without its metadata, instruction names
        # blanked (XLA numbers them in creation order), the debug-info
        # tables between the module header and the first computation cut
        txt = re.sub(r"\\nFileNames\\n.*?\\n\\n(?=%|ENTRY)", "\\n", txt,
                     count=1, flags=re.S)
        txt = re.sub(r",? ?metadata=[{][^}]*[}]", "", txt)
        return sorted(re.sub(r"%[\\w.-]+", "%_", line)
                      for line in txt.splitlines() if " = " in line)

    ins_on, ins_off = instructions(on), instructions(off)
    coll = re.compile(r"(all-to-all|all-reduce|all-gather|reduce-scatter"
                      r"|collective-permute)(-start)?[(]")
    colls = [i for i in ins_on if coll.search(i)]
    assert colls == [i for i in ins_off if coll.search(i)]
    assert any("all-to-all(" in c for c in colls), colls
    assert ins_on == ins_off
    print("scopes free", len(colls))
    """, devices=4)
    assert "scopes free" in out


@pytest.mark.tier1
def test_scope_set_same_on_the_four_moe_paths():
    """Scopes live in the shared helpers, so _moe_local, _moe_a2a,
    _moe_a2a_ragged and _moe_psum all name router, dispatch, experts and
    combine, forward and backward, and the two all-to-all paths also the
    exchange."""
    out = du.run("""
    import re
    import jax
    import dist_utils as du
    from repro.core import fmoe

    mesh = du.make_mesh(1, 4)
    MOE = {"fmoe.router", "fmoe.dispatch", "fmoe.experts", "fmoe.combine"}

    def scopes_of(env, dist):
        def loss(p):
            y, m = fmoe.fmoe_apply(p, env.x, env.cfg, dist=dist)
            return (y ** 2).mean() + 0.01 * (m.aux_loss + m.z_loss)
        with mesh:
            txt = jax.jit(jax.grad(loss)).lower(env.params).compile().as_text()
        names = re.findall(r'op_name="([^"]*)"', txt)
        back = {m for n in names if "transpose(" in n
                for m in re.findall(r"fmoe[.][a-z_]+", n)}
        return {m for n in names for m in re.findall(r"fmoe[.][a-z_]+", n)
                } - {"fmoe.py"}, back

    cap, rag = du.moe_env(), du.moe_env(dispatch="ragged")
    paths = {
        "local": (cap, None, "local"),
        "a2a": (cap, fmoe.DistConfig(mesh, ("data", "model")), "a2a"),
        "ragged": (rag, fmoe.DistConfig(mesh, ("data", "model")), "a2a"),
        "psum": (cap, fmoe.DistConfig(mesh, ("data",)), "psum"),
    }
    for name, (env, dist, mode) in paths.items():
        assert dist is None or dist.mode == mode, (name, dist.mode)
        got, back = scopes_of(env, dist)
        want = MOE | ({"fmoe.exchange"} if name in ("a2a", "ragged")
                      else set())
        assert got == want, (name, got)
        assert back >= MOE - {"fmoe.dispatch"}, (name, back)
    print("scope sets ok")
    """, devices=4)
    assert "scope sets ok" in out


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock
# ---------------------------------------------------------------------------


def _host_spans(log_dir: str, names) -> list:
    """[(name, start, end)] of the host-plane events called one of ``names``
    in the profiler trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name in names]
    return sorted(out, key=lambda ev: ev[1])


def test_tracer_nesting_and_export_roundtrip(tmp_path):
    """Spans on: each is a TraceAnnotation in the profiler trace's host
    plane, nested as entered, on the trace's one clock."""
    assert not obs_trace.enabled()  # off by default
    log_dir = str(tmp_path / "trace")
    f = jax.jit(lambda x: x * 2)
    try:
        obs_trace.configure(enabled=True)
        jax.profiler.start_trace(log_dir)
        with obs_trace.span("outer", step=1):
            with obs_trace.span("inner"):
                f(jnp.ones(3)).block_until_ready()
        jax.profiler.stop_trace()
    finally:
        obs_trace.configure(enabled=False)
    spans = _host_spans(log_dir, {"outer", "inner"})
    assert [n for n, _, _ in spans] == ["outer", "inner"]
    (_, os_, oe), (_, is_, ie) = spans
    assert os_ <= is_ < ie <= oe


def test_tracer_disabled_is_noop_and_ring_bounded(tmp_path):
    """Spans off: a no-op that leaves nothing in a running trace.  The
    CLIs' Window records exactly its steps."""
    log_dir = str(tmp_path / "off")
    jax.profiler.start_trace(log_dir)
    with obs_trace.span("x", step=0) as s:
        assert s is None
    jax.profiler.stop_trace()
    assert _host_spans(log_dir, {"x"}) == []

    log_dir = str(tmp_path / "window")
    w = obs_trace.Window(log_dir, first=2, steps=2)
    try:
        obs_trace.configure(enabled=True)
        for step in range(6):
            w.at(step)
            with obs_trace.span(f"s{step}"):
                pass
        w.close()
    finally:
        obs_trace.configure(enabled=False)
    got = _host_spans(log_dir, {f"s{i}" for i in range(6)})
    assert [n for n, _, _ in got] == ["s2", "s3"]
    assert not w.on


# ---------------------------------------------------------------------------
# Metrics sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_roundtrip_and_append(tmp_path):
    p = str(tmp_path / "m.jsonl")
    with obs_sink.JsonlSink(p) as s:
        s.emit({"kind": "a", "v": jnp.float32(1.5), "arr": np.arange(3)})
    with obs_sink.JsonlSink(p, append=True) as s:
        s.emit({"kind": "b", "v": 2})
    recs = obs_sink.jsonl_records(p)
    assert recs == [{"kind": "a", "v": 1.5, "arr": [0, 1, 2]},
                    {"kind": "b", "v": 2}]  # device values coerced to Python


def test_csv_sink_locks_columns(tmp_path):
    p = str(tmp_path / "m.csv")
    with obs_sink.CsvSink(p) as s:
        s.emit({"a": 1, "b": 2})
        s.emit({"a": 3, "b": 4, "c": 5})  # extra key dropped
        s.emit({"a": 6})  # missing key left empty
    lines = open(p).read().strip().splitlines()
    assert lines[0] == "a,b"
    assert lines[1:] == ["1,2", "3,4", "6,"]


def test_memory_and_multi_sink():
    mem = obs_sink.MemorySink(capacity=2)
    for i in range(5):
        mem.emit({"i": i})
    assert [r["i"] for r in mem.records] == [3, 4]  # bounded ring

    a, b = obs_sink.MemorySink(), obs_sink.MemorySink()
    multi = obs_sink.MultiSink(a, None, b)  # None sinks are skipped
    multi.emit({"x": jnp.float32(2.0)})
    assert a.records == b.records == [{"x": 2.0}]


# ---------------------------------------------------------------------------
# LoadMonitor: bounded history + sink emission
# ---------------------------------------------------------------------------


def _fake_metrics(E=8, drop=0.25):
    load = np.ones(E)
    load[0] = 2.0
    return SimpleNamespace(load=load, drop_frac=drop)


def test_load_monitor_history_bounded_and_sink_fed():
    sink = obs_sink.MemorySink()
    mon = LoadMonitor(8, history_cap=4, record_every=1, sink=sink)
    for _ in range(10):
        mon.update(_fake_metrics())
    assert len(mon.history) == 4  # ring: old snapshots evicted
    assert mon.history[-1]["step"] == 10
    assert len(sink.records) == 10  # sink saw every recorded snapshot
    assert all(r["kind"] == "load_monitor" for r in sink.records)
    assert sink.records[-1]["imbalance"] > 1.0


def test_load_monitor_record_every_default_and_override():
    mon = LoadMonitor(8, record_every=2)
    for _ in range(6):
        mon.update(_fake_metrics())  # instance default cadence
    assert [r["step"] for r in mon.history] == [2, 4, 6]
    mon.update(_fake_metrics(), record_every=7)
    assert [r["step"] for r in mon.history] == [2, 4, 6, 7]
    mon2 = LoadMonitor(8)  # record_every=0: never records, never grows
    for _ in range(5):
        mon2.update(_fake_metrics())
    assert len(mon2.history) == 0


# ---------------------------------------------------------------------------
# StepStats: measured counters vs modeled HLO bytes
# ---------------------------------------------------------------------------


def test_step_stats_record_and_wire_ratio():
    st = StepStats("train_step", 3, 0.5,
                   counters={"wire_bytes": 50.0, "loss": 1.25},
                   modeled={"all-to-all": 80, "collective-permute": 20,
                            "all-reduce": 999})
    assert st.measured_wire_bytes == 50.0
    assert st.modeled_wire_bytes == 100.0  # a2a + cp only; all-reduce is not wire
    assert st.wire_ratio == 0.5
    rec = st.record()
    assert rec["kind"] == "train_step" and rec["step"] == 3
    assert rec["wall_s"] == 0.5 and rec["loss"] == 1.25
    assert rec["modeled_all_to_all_bytes"] == 80
    assert rec["modeled_all_reduce_bytes"] == 999
    assert rec["wire_measured_over_modeled"] == 0.5

    empty = StepStats("s", 0, 0.1)
    assert empty.measured_wire_bytes is None and empty.wire_ratio is None
    assert "wire_measured_over_modeled" not in empty.record()


def test_modeled_collective_bytes_parses_hlo_text():
    txt = ("%a = f32[128,32]{1,0} all-to-all(%x), dimensions={0}\n"
           "%b = bf16[64]{0} collective-permute-start(%y)\n")
    cb = modeled_collective_bytes(txt)
    assert cb == {"all-to-all": 128 * 32 * 4, "collective-permute": 64 * 2}


# ---------------------------------------------------------------------------
# Serve + train integration
# ---------------------------------------------------------------------------


def test_serve_step_with_metrics_single_device():
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.launch.serve import make_serve_step
    from repro.models import lm

    cfg = reduced(get_config("fastmoe-gpt"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    cache = lm.init_cache(cfg, 1, cache_len=8)
    tok = jnp.zeros((1, 1), jnp.int32)

    step = make_serve_step(cfg, with_metrics=True)
    logits, cache, md = step(params, tok, jnp.int32(0), cache)
    assert set(md) >= {"drop_frac", "wire_elems", "wire_bytes", "dropped",
                       "shadow_hits", "imbalance"}
    assert float(md["wire_bytes"]) == 0.0  # single device: no wire
    assert float(md["imbalance"]) == 1.0

    plain = make_serve_step(cfg, with_metrics=False)
    _, _, md = plain(params, tok, jnp.int32(0),
                     lm.init_cache(cfg, 1, cache_len=8))
    assert md == {}  # fixed 3-tuple arity: empty metrics, never a 2-tuple


@pytest.mark.tier1
def test_train_cli_metrics_out_and_trace(tmp_path):
    """--metrics_out/--trace end to end on a 1x2 mesh: per-step JSONL
    records carrying the device wire counters + a profiler trace whose host
    plane holds the train_step span of the step after the first."""
    mpath = str(tmp_path / "metrics.jsonl")
    tpath = str(tmp_path / "trace")
    out = du.run_cli(
        ["repro.launch.train", "--arch", "fastmoe-gpt", "--reduced",
         "--steps", "2", "--batch", "4", "--seq", "32", "--mesh", "1x2",
         "--log_every", "1", "--metrics_out", mpath, "--trace", tpath],
        devices=2)
    assert "done: 2 steps" in out, out

    recs = obs_sink.jsonl_records(mpath)
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert [r["step"] for r in steps] == [0, 1]
    for r in steps:
        assert r["wall_s"] > 0
        assert r["wire_bytes"] > 0  # distributed a2a: wire traffic measured
        assert r["wire_elems"] > 0
        assert "loss" in r and "imbalance" in r
        # modeled HLO bytes rode along (AOT-lowered step)
        assert any(k.startswith("modeled_") for k in r)

    spans = _host_spans(tpath, {"train_step"})
    assert len(spans) == 1 and spans[0][2] > spans[0][1]
    assert glob.glob(os.path.join(tpath, "plugins", "profile", "*",
                                  "perfetto_trace.json.gz"))
