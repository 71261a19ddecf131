"""Executor equivalence + dynamic-rate semantics (paper §3.3), on the
unified ``NetworkBuilder`` + ``Network.compile(ExecutionPlan)`` surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ExecutionPlan, NetworkBuilder, RuntimeMode,
                        dynamic_actor, map_fire, static_actor)


def make_chain(n_iter=8, rate=2, delay=True):
    tok = (3,)

    def src_fire(state, inputs, rates):
        data, idx = state
        return (data, idx + 1), {
            "out": jax.lax.dynamic_slice_in_dim(data, idx * rate, rate, 0)}

    src = static_actor(
        "src", (), ("out",), src_fire,
        init=lambda: (jnp.arange(n_iter * rate * 3, dtype=jnp.float32)
                      .reshape(n_iter * rate, 3), jnp.int32(0)),
        ready=lambda st: st[1] < n_iter)
    dbl = static_actor("dbl", ("in",), ("out",),
                       map_fire(lambda w: w * 2.0, "in", "out"))

    def sink_fire(state, inputs, rates):
        data, idx = state
        return (jax.lax.dynamic_update_slice_in_dim(data, inputs["in"],
                                                    idx * rate, 0), idx + 1), {}

    snk = static_actor(
        "snk", ("in",), (), sink_fire,
        init=lambda: (jnp.zeros((n_iter * rate, 3), jnp.float32), jnp.int32(0)),
        finish=lambda st: st[0])
    b = NetworkBuilder()
    b.actors(src, dbl, snk)
    b.connect("src.out", "dbl.in", rate=rate, token_shape=tok, name="f1")
    b.connect("dbl.out", "snk.in", rate=rate, token_shape=tok,
              delay=1 if delay else 0, name="f2")
    net = b.build()
    data = 2 * np.arange(n_iter * rate * 3, dtype=np.float32).reshape(-1, 3)
    expect = (np.concatenate([np.zeros((1, 3), np.float32), data[:-1]])
              if delay else data)
    return net, expect


@pytest.mark.parametrize("delay", [False, True])
def test_three_executors_agree(delay):
    net, expect = make_chain(delay=delay)
    p1 = net.compile(mode="static", n_iterations=8)
    np.testing.assert_allclose(
        np.asarray(p1.collect("snk", p1.run().state)), expect)
    p2 = net.compile(ExecutionPlan(mode="dynamic"))
    r2 = p2.run()
    np.testing.assert_allclose(np.asarray(p2.collect("snk", r2.state)), expect)
    assert all(int(v) == 8 for v in r2.fire_counts.values())
    p3 = net.compile(mode="interpreted", n_iterations=8)
    np.testing.assert_allclose(
        np.asarray(p3.collect("snk", p3.run().state)), expect)


def make_gated(n=9, period=3):
    """ctl enables gate every `period`-th firing (dynamic data rates)."""
    r, tok = 2, (3,)

    def ctl_fire(state, inputs, rates):
        return state + 1, {"out": (state % period == 0).astype(jnp.int32).reshape(1)}

    ctl = static_actor("ctl", (), ("out",), ctl_fire, init=lambda: jnp.int32(0),
                       ready=lambda st: st < n)

    def gctl(tok):
        on = tok[0] > 0
        return {"in": on, "out": on}

    gate = dynamic_actor("gate", "c", gctl, ("in",), ("out",),
                         map_fire(lambda w: w + 100.0, "in", "out"))
    n_pass = (n + period - 1) // period

    def src_fire(state, inputs, rates):
        data, idx = state
        return (data, idx + 1), {
            "out": jax.lax.dynamic_slice_in_dim(data, idx * r, r, 0)}

    src = static_actor(
        "src", (), ("out",), src_fire,
        init=lambda: (jnp.arange(n * r * 3, dtype=jnp.float32).reshape(n * r, 3),
                      jnp.int32(0)),
        ready=lambda st: st[1] < n_pass)

    def sink_fire(state, inputs, rates):
        data, idx = state
        return (jax.lax.dynamic_update_slice_in_dim(data, inputs["in"],
                                                    idx * r, 0), idx + 1), {}

    snk = static_actor(
        "snk", ("in",), (), sink_fire,
        init=lambda: (jnp.zeros((n * r, 3), jnp.float32), jnp.int32(0)),
        finish=lambda st: st[0])
    b = NetworkBuilder()
    b.actors(ctl, src, gate, snk)
    b.connect("ctl.out", "gate.c", name="fc")          # control: inferred
    b.connect("src.out", "gate.in", rate=r, token_shape=tok, name="f1")
    b.connect("gate.out", "snk.in", rate=r, token_shape=tok, name="f2")
    return b.build(), n_pass


def test_dynamic_gate_consumes_only_when_enabled():
    net, n_pass = make_gated()
    prog = net.compile(ExecutionPlan(mode="dynamic"))
    result = prog.run()
    counts = result.fire_counts
    # gate fires on every control token; src only supplies enabled windows
    assert int(counts["gate"]) == 9
    assert int(counts["src"]) == n_pass
    assert int(counts["snk"]) == n_pass
    out = np.asarray(prog.collect("snk", result.state))
    data = np.arange(9 * 2 * 3, dtype=np.float32).reshape(-1, 3)
    np.testing.assert_allclose(out[:2], data[0:2] + 100.0)


def test_static_dal_mode_rejects_dynamic_actors():
    """DAL's OpenCL path is SDF-only (paper §2.3) — dynamic actors must be
    rejected on the accelerated path."""
    net, _ = make_gated()
    with pytest.raises(ValueError, match="STATIC_DAL"):
        net.compile(ExecutionPlan(mode="dynamic",
                                  runtime_mode=RuntimeMode.STATIC_DAL))
    # ... but a static network passes.
    chain, _ = make_chain()
    chain.compile(mode="static", n_iterations=2,
                  runtime_mode=RuntimeMode.STATIC_DAL)


def test_heterogeneous_split():
    """GPP/GPU partition (paper §3.3): middle actor accelerated, source and
    sink on host; boundary channels become feed/fetch actors.  The raw
    mapping API — Program.stream wraps this (tests/test_program_api.py)."""
    from repro.core import collect_sink, heterogeneous_split, stage_feed
    net, expect = make_chain(delay=False)
    sub, feeds, fetches = heterogeneous_split(net, ["dbl"], n_iterations=8)
    assert feeds == ["__feed_f1"] and fetches == ["__fetch_f2"]
    state = sub.init_state()
    data = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(8, 2, 3)
    state = stage_feed(state, "__feed_f1", data)
    out_state = sub.compile(mode="static", n_iterations=8).run(state).state
    got = np.asarray(collect_sink(sub, out_state, "__fetch_f2"))
    np.testing.assert_allclose(got.reshape(-1, 3),
                               2 * np.asarray(data).reshape(-1, 3))


@pytest.mark.parametrize("mode", ["static", "dynamic"])
def test_large_closed_over_array_is_a_runner_argument(monkeypatch, mode):
    """An actor closing over a >= 1 MiB array (an LM's weights in the
    serving graph) runs with the array passed to the compiled program as
    an argument, not embedded as a constant; results are unchanged."""
    import repro.core.executor as ex
    hoisted = []
    real = ex._hoist
    monkeypatch.setattr(ex, "_hoist",
                        lambda closed, big, *a: hoisted.append(sum(big))
                        or real(closed, big, *a))
    n, d = 4, 512
    w = jax.random.normal(jax.random.PRNGKey(0), (d, d), jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.float32)

    def src_fire(state, inputs, rates):
        return state + 1, {"out": jax.lax.dynamic_slice_in_dim(xs, state, 1)}

    src = static_actor("src", (), ("out",), src_fire, init=lambda: jnp.int32(0),
                       ready=lambda st: st < n)
    mm = static_actor("mm", ("in",), ("out",),
                      map_fire(lambda t: t @ w, "in", "out"))

    def sink_fire(state, inputs, rates):
        data, idx = state
        return (jax.lax.dynamic_update_slice_in_dim(data, inputs["in"], idx, 0),
                idx + 1), {}

    snk = static_actor("snk", ("in",), (), sink_fire,
                       init=lambda: (jnp.zeros((n, d), jnp.float32),
                                     jnp.int32(0)),
                       finish=lambda st: st[0])
    b = NetworkBuilder()
    b.actors(src, mm, snk)
    b.connect("src.out", "mm.in", token_shape=(d,), name="f1")
    b.connect("mm.out", "snk.in", token_shape=(d,), name="f2")
    prog = b.build().compile(ExecutionPlan(
        mode=mode, n_iterations=n if mode == "static" else None))
    for _ in range(2):
        got = np.asarray(prog.collect("snk", prog.run().state))
        np.testing.assert_allclose(got, np.asarray(xs) @ np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    assert hoisted == [1]
