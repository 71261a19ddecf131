"""Firing-level tracing and occupancy profiles.

Three contracts pinned here:

  * **Observer effect is zero** — trace=True runs are bit-identical in
    states / cursors / fire counts / sweeps to untraced runs on every
    traceable backend (host dynamic, single-core megakernel, grid k=2)
    across the three workload families (DPD, MoE, serving).
  * **The export is honest** — the Chrome trace-event JSON's per-actor
    firing events exactly equal ``RunResult.fire_counts``, and the
    document validates against the trace-event schema (required keys,
    monotonic timestamps per track).
  * **Profiles drive partitions** — ``cut_objective="profile"`` over a
    measured :class:`repro.core.trace.Profile` yields a valid contiguous
    partition whose results stay bit-identical (Kahn determinism: the
    cut moves work, never values).

Plus the satellite oracles: ``diagnostics.high_water`` of a clean
guarded dynamic run equals an eager queue-replay oracle, and
``ProgramStats.to_json()`` round-trips through ``json``.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import (ExecutionPlan, NetworkBuilder, map_fire,
                        static_actor, validate_chrome_trace)
from repro.core.executor import _can_fire, _max_fireable, fire_actor
from repro.graphs.factories import make_dpd, make_moe, states_identical

BACKENDS = ("dynamic", "megakernel", "grid2")


def _plan(backend, **kw):
    if backend == "dynamic":
        return ExecutionPlan(mode="dynamic", **kw)
    cores = {"megakernel": 1, "grid2": 2}[backend]
    return ExecutionPlan(mode="megakernel", specialize=False, cores=cores,
                         **kw)


@pytest.fixture(scope="module")
def dpd():
    net, _ = make_dpd(n_firings=4, block_l=64)
    return net


@pytest.fixture(scope="module")
def moe():
    net, _ = make_moe(3)
    return net


@pytest.fixture(scope="module")
def serving():
    from repro.configs import smoke_config
    from repro.models import init_params
    from repro.serve import ActorEngine, Request, ServeConfig

    cfg = smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab,
                                        size=int(n)).astype(np.int32),
                    max_new=m)
            for n, m in [(5, 3), (3, 2), (6, 3)]]
    eng = ActorEngine(cfg, params,
                      ServeConfig(batch_size=2, max_prompt=8, max_new=3,
                                  eos_id=7))
    return eng.build_network(reqs)


# --------------------------------------------------------------------------- #
# Off-path identity: the trace observes, it never schedules.
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("graph", ("dpd", "moe", "serving"))
def test_trace_off_path_bit_identical(request, graph, backend):
    net = request.getfixturevalue(graph)
    off = net.compile(_plan(backend)).run()
    on = net.compile(_plan(backend, trace=True)).run()
    assert states_identical(off.state, on.state)
    assert int(off.sweeps) == int(on.sweeps)
    assert {k: int(v) for k, v in off.fire_counts.items()} \
        == {k: int(v) for k, v in on.fire_counts.items()}
    assert off.trace is None
    assert on.trace is not None and on.trace.n_events > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_trace_firing_counts_match_fire_counts(dpd, backend):
    res = dpd.compile(_plan(backend, trace=True)).run()
    fc = res.trace.firing_counts()
    assert fc == {k: int(v) for k, v in res.fire_counts.items()}
    # Attempts dominate firings (skipped visits are events too).
    att = res.trace.attempt_counts()
    assert all(att[k] >= fc[k] for k in fc)


# --------------------------------------------------------------------------- #
# Perfetto export (ISSUE acceptance: exported per-actor firing events ==
# RunResult.fire_counts on a traced DPD megakernel run).
# --------------------------------------------------------------------------- #
def test_perfetto_firing_events_equal_fire_counts(dpd):
    res = dpd.compile(_plan("megakernel", trace=True)).run()
    doc = res.trace.to_perfetto()
    names = res.trace.actor_names
    fired = {nm: 0 for nm in names}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            fired[names[ev["tid"] - 1]] += 1
    assert fired == {k: int(v) for k, v in res.fire_counts.items()}


def test_perfetto_export_validates_and_writes(dpd, tmp_path):
    res = dpd.compile(_plan("dynamic", trace=True)).run()
    path = tmp_path / "dpd.trace.json"
    res.trace.to_perfetto(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == []
    phs = {ev["ph"] for ev in doc["traceEvents"]}
    assert {"M", "X", "C"} <= phs          # tracks, firings, occupancy
    counters = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "C"}
    assert counters == {f"occ:{f}" for f in res.trace.fifo_names}
    assert doc["otherData"]["dropped_events"] == 0


def test_validate_chrome_trace_flags_garbage():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 0, "tid": 1, "ts": 5.0, "dur": 1.0},
        {"name": "a", "ph": "X", "pid": 0, "tid": 1, "ts": 2.0, "dur": 1.0},
        {"name": "b", "ph": "C", "pid": 0, "ts": 0.0},   # no args
    ]}
    problems = validate_chrome_trace(bad)
    assert any("monotonic" in p or "ts" in p for p in problems)
    assert any("args" in p for p in problems)
    assert validate_chrome_trace({"nope": 1}) != []


def test_grid_trace_carries_core_assignment(dpd):
    res = dpd.compile(_plan("grid2", trace=True)).run()
    tr = res.trace
    assert tr.actor_cores is not None
    assert set(tr.actor_cores) == {0, 1}
    doc = tr.to_perfetto()
    thread_names = [ev["args"]["name"] for ev in doc["traceEvents"]
                    if ev["ph"] == "M" and ev["name"] == "thread_name"]
    assert any("[core 1]" in n for n in thread_names)


# --------------------------------------------------------------------------- #
# Ring semantics: fixed capacity, oldest events dropped, count honest.
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ("dynamic", "megakernel"))
def test_trace_ring_wraps_keeping_newest(dpd, backend):
    full = dpd.compile(_plan(backend, trace=True)).run().trace
    assert full.dropped == 0
    cap = 8
    small = dpd.compile(
        _plan(backend, trace=True, trace_capacity=cap)).run().trace
    assert small.n_events == cap
    assert small.dropped == full.n_events - cap
    # The survivors are exactly the newest `cap` attempts.
    np.testing.assert_array_equal(small.events, full.events[-cap:])


# --------------------------------------------------------------------------- #
# Profiles -> partition weights (ISSUE acceptance: valid contiguous cut,
# bit-identical results, k in {2, 4}).
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("cores", (2, 4))
def test_profile_cut_valid_and_bit_identical(dpd, cores):
    prof = dpd.compile(_plan("dynamic", trace=True)).run().trace.profile()
    w = prof.as_cut_weights()
    assert set(w) == {"actors", "channels"}
    assert all(v >= 1 for v in w["actors"].values())

    base = dpd.compile(ExecutionPlan(
        mode="megakernel", specialize=False, cores=cores)).run()
    prog = dpd.compile(ExecutionPlan(
        mode="megakernel", specialize=False, cores=cores,
        cut_objective="profile", profile=prof))
    res = prog.run()
    assert states_identical(base.state, res.state)
    assert {k: int(v) for k, v in base.fire_counts.items()} \
        == {k: int(v) for k, v in res.fire_counts.items()}
    st = prog.stats()
    assert st.cut_objective == "profile"
    assert st.grid_cores == cores
    # Valid partition: every core non-empty, and the concatenation is the
    # declaration order (contiguous cut).
    assert all(len(g) > 0 for g in st.partition_actors)
    flat = tuple(nm for g in st.partition_actors for nm in g)
    assert flat == tuple(dpd.actors)


def test_profile_plan_validation(dpd):
    # Cross-field rules (trace-vs-mode, trace_capacity-vs-trace,
    # profile-vs-cut_objective) are judged by ExecutionPlan.validate at
    # compile time; only value checks (trace_capacity=0) stay at
    # construction.
    with pytest.raises(ValueError, match="trace"):
        dpd.compile(ExecutionPlan(mode="static", n_iterations=4,
                                  trace=True))
    with pytest.raises(ValueError, match="trace_capacity"):
        dpd.compile(ExecutionPlan(mode="dynamic", trace_capacity=64))
    with pytest.raises(ValueError, match="trace_capacity"):
        ExecutionPlan(mode="dynamic", trace=True, trace_capacity=0)
    with pytest.raises(ValueError, match="profile"):
        dpd.compile(ExecutionPlan(mode="megakernel", cores=2,
                                  cut_objective="profile"))
    with pytest.raises(ValueError, match="profile"):
        dpd.compile(ExecutionPlan(mode="megakernel", cores=2,
                                  profile={"actors": {"a": 1}}))
    # A mapping form works, and the frozen plan survives replace().
    plan = ExecutionPlan(mode="megakernel", cores=2,
                         cut_objective="profile",
                         profile={"actors": {"a": 2}, "channels": {}})
    again = dataclasses.replace(plan, cores=4)
    assert again.profile == plan.profile


# --------------------------------------------------------------------------- #
# Streaming and serving carry traces.
# --------------------------------------------------------------------------- #
def _stream_net():
    import jax.numpy as jnp
    b = NetworkBuilder()
    b.actor(static_actor("src", (), ("out",),
                         lambda st, ins, rates: (st,
                                                 {"out": jnp.zeros((4, 8))})))
    b.actor(static_actor("amp", ("in",), ("out",),
                         map_fire(lambda w: 2.0 * w, "in", "out")))
    b.actor(static_actor("sink", ("in",), (),
                         lambda st, ins, rates: (st, {})))
    b.connect("src.out", "amp.in", rate=4, token_shape=(8,), name="f_in")
    b.connect("amp.out", "sink.in", rate=4, token_shape=(8,), name="f_out")
    return b.build()


def test_stream_merges_chunk_traces():
    net = _stream_net()
    prog = net.compile(ExecutionPlan(mode="dynamic", n_iterations=2,
                                     accelerated=("amp",), trace=True))
    feeds = np.arange(6 * 4 * 8, dtype=np.float32).reshape(6, 4, 8)
    prog.stream({"f_in": feeds})
    tr = prog.last_stream_trace
    assert tr is not None
    # 3 chunks x 2 windows each: the merged trace reads as one run.
    assert tr.firing_counts()["amp"] == 6
    sweeps = tr.events[:, 1]
    assert (np.diff(sweeps) >= 0).all()    # chunk offsets keep order
    # An untraced stream leaves no stale merged trace behind.
    prog2 = net.compile(ExecutionPlan(mode="dynamic", n_iterations=2,
                                      accelerated=("amp",)))
    prog2.stream({"f_in": feeds})
    assert prog2.last_stream_trace is None


def test_actor_engine_exposes_last_trace():
    from repro.configs import smoke_config
    from repro.models import init_params
    from repro.serve import ActorEngine, Request, ServeConfig

    cfg = smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=4).astype(np.int32),
                    max_new=2) for _ in range(2)]
    eng = ActorEngine(cfg, params,
                      ServeConfig(batch_size=2, max_prompt=8, max_new=2,
                                  eos_id=7),
                      plan=ExecutionPlan(mode="dynamic", trace=True))
    eng.generate(reqs)
    assert eng.last_trace is not None
    assert eng.last_trace.firing_counts() == eng.last_fire_counts


# --------------------------------------------------------------------------- #
# Satellite: high-water marks vs an eager queue-replay oracle.
# --------------------------------------------------------------------------- #
def _oracle_high_water(net):
    """Replay the dynamic multi-firing schedule eagerly, tracking each
    channel's max post-write occupancy — an independent queue oracle for
    the guards' ``mark_high_water`` (which records occ after every
    masked write, enabled or not, of the fired actor's out ports)."""
    state = net.init_state()
    fnames = list(net.fifos)
    hw = {f: 0 for f in fnames}
    fired_any = True
    while fired_any:
        fired_any = False
        for nm in net.actors:
            k = int(_max_fireable(net, nm, state))
            for _ in range(k):
                if not bool(_can_fire(net, nm, state)):
                    break
                state = fire_actor(net, nm, state)
                fired_any = True
                for _, _, fi in net.out_port_specs[nm]:
                    hw[fnames[fi]] = max(hw[fnames[fi]],
                                         int(state.fifos[fi].occ))
    return hw


def test_high_water_matches_queue_oracle(dpd):
    res = dpd.compile(_plan("dynamic", guards=True)).run()
    assert res.diagnostics.ok
    assert res.diagnostics.high_water == _oracle_high_water(dpd)


# --------------------------------------------------------------------------- #
# Satellite: stats().to_json() committed schema round-trips.
# --------------------------------------------------------------------------- #
def test_stats_to_json_roundtrip(dpd):
    prog = dpd.compile(_plan("grid2", trace=True))
    prog.run()
    doc = prog.stats().to_json()
    # v2 bumped for the sharding fields; v1 consumers keep working
    # because every v1 key survives unchanged (checked below).
    assert doc["schema_version"] == 2
    field_names = {f.name for f in dataclasses.fields(prog.stats())}
    assert field_names <= set(doc)
    # Grid fields exercised (tuples lowered to lists) and JSON-stable.
    assert doc["grid_cores"] == 2
    assert isinstance(doc["partition_actors"], list)
    assert json.loads(json.dumps(doc)) == doc


_STATS_V1_KEYS = {
    "schema_version", "mode", "grid_cores", "partition_actors",
    "cut_objective",
}


def test_stats_schema_v2_superset_of_v1(dpd):
    """Schema v2 adds the sharding telemetry without renaming or
    removing anything a v1 reader consumed — and the single-device
    defaults are inert (devices=1, collectives None)."""
    prog = dpd.compile(_plan("dynamic"))
    prog.run()
    doc = prog.stats().to_json()
    assert doc["schema_version"] == 2
    assert _STATS_V1_KEYS <= set(doc)
    assert {"devices", "device_partition_actors",
            "collective_bytes_per_sweep",
            "quiescence_allreduces"} <= set(doc)
    assert doc["devices"] == 1
    assert doc["device_partition_actors"] is None
    assert doc["collective_bytes_per_sweep"] is None
    assert doc["quiescence_allreduces"] is None
    assert json.loads(json.dumps(doc)) == doc


@pytest.mark.parametrize("old_payload", [False, True])
def test_trace_snapshot_payload_round_trips(dpd, old_payload):
    """Snapshot payloads carry no wall clock; an older payload that does
    still reads back (the entry is ignored)."""
    from repro.core.program import _trace_from_payload, _trace_to_payload
    t = dpd.compile(_plan("dynamic", trace=True)).run().trace
    payload = _trace_to_payload(t)
    assert "wall_time_s" not in payload
    if old_payload:
        payload["wall_time_s"] = 0.25
    back = _trace_from_payload(payload)
    np.testing.assert_array_equal(back.events, t.events)
    assert dataclasses.replace(back, events=t.events) == dataclasses.replace(
        t, events=t.events)
    assert back.profile().as_cut_weights() == t.profile().as_cut_weights()
