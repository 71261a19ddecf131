"""Quickstart: the paper's MoC in ~60 lines.

Builds a tiny dynamic-data-rate network with the declarative
``NetworkBuilder`` — a control actor gates an amplifier actor (token rate
0 or r per firing) — compiles it under an ``ExecutionPlan``, and shows
the rate-0 firings genuinely skipping work.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ExecutionPlan, Mode, NetworkBuilder, dynamic_actor,
                        map_fire, static_actor)

N_FIRINGS, RATE, TOK = 8, 2, (4,)


def main():
    # Source: emits windows of RATE tokens from a staged array.
    def src_fire(state, inputs, rates):
        data, idx = state
        win = jax.lax.dynamic_slice_in_dim(data, idx * RATE, RATE, axis=0)
        return (data, idx + 1), {"out": win}

    n_enabled = (N_FIRINGS + 1) // 2
    source = static_actor(
        "source", (), ("out",), src_fire,
        init=lambda: (jnp.arange(N_FIRINGS * RATE * 4, dtype=jnp.float32)
                      .reshape(N_FIRINGS * RATE, 4), jnp.int32(0)),
        ready=lambda st: st[1] < n_enabled)

    # Control actor: enables the amplifier on every second firing.
    def ctl_fire(state, inputs, rates):
        return state + 1, {"out": (state % 2 == 0).astype(jnp.int32).reshape(1)}

    control = static_actor("control", (), ("out",), ctl_fire,
                           init=lambda: jnp.int32(0),
                           ready=lambda st: st < N_FIRINGS)

    # Dynamic actor: the control token pins its ports to rate 0 or RATE.
    amp = dynamic_actor(
        "amp", "c", lambda tok: {"in": tok[0] > 0, "out": tok[0] > 0},
        ("in",), ("out",), map_fire(lambda w: 10.0 * w, "in", "out"))

    def sink_fire(state, inputs, rates):
        data, idx = state
        return (jax.lax.dynamic_update_slice_in_dim(
            data, inputs["in"], idx * RATE, axis=0), idx + 1), {}

    sink = static_actor(
        "sink", ("in",), (), sink_fire,
        init=lambda: (jnp.zeros((N_FIRINGS * RATE, 4), jnp.float32),
                      jnp.int32(0)),
        finish=lambda st: st[0])

    # Declarative wiring: one connect() per channel; the control channel is
    # inferred from amp's control port, Eq. 1 capacities are derived.
    b = NetworkBuilder()
    b.actors(source, control, amp, sink)
    b.connect("control.out", "amp.c")                        # control (1,) i32
    b.connect("source.out", "amp.in", rate=RATE, token_shape=TOK)
    b.connect("amp.out", "sink.in", rate=RATE, token_shape=TOK)
    net = b.build()

    print("channel capacities (Eq. 1):",
          {f.name: f.capacity_tokens for f in net.fifos.values()})
    print("--- Graphviz (net.to_dot(), paste into any dot viewer) ---")
    print(net.to_dot())

    prog = net.compile(ExecutionPlan(mode="dynamic"))  # one XLA program
    result = prog.run()
    out = np.asarray(prog.collect("sink"))
    print("firings:", {k: int(v) for k, v in result.fire_counts.items()},
          f"in {int(result.sweeps)} sweeps")
    print("first enabled window (x10):", out[0:RATE, 0])
    assert np.allclose(out[0:RATE], 10.0 * np.arange(RATE * 4).reshape(RATE, 4))
    print("OK — dynamic data rates on the compiled path.")

    # Same network as ONE persistent Pallas kernel: buffered ring
    # buffers live in kernel scratch, the token-driven sweep loop runs
    # on the device (interpret mode off-TPU).  Bit-identical to the
    # dynamic executor — and transient channels (provably drained every
    # iteration) are FORWARDED as loop-carried windows instead of
    # scratch rings: the scratch diet, visible in the stats.
    mega = net.compile(ExecutionPlan(mode=Mode.MEGAKERNEL))
    mresult = mega.run()
    stats = mega.stats()
    assert np.array_equal(np.asarray(mega.collect("sink")), out)
    print(f"megakernel: {int(mresult.sweeps)} sweeps on-device, "
          f"{stats.scratch_bytes} B scratch vs "
          f"{stats.hbm_state_bytes} B HBM state")
    print(f"  transient forwarding: {len(stats.forwarded_fifos)} of "
          f"{stats.n_fifos} channels -> loop-carried windows, "
          f"{stats.reclaimed_scratch_bytes} B of rings reclaimed "
          f"({', '.join(stats.forwarded_fifos)})")

    # And grid-parallel: the firing table split across 2 cores (paper
    # §3.3 actor-to-core mapping), partition-crossing channels guarded
    # by shared cursor semaphores.  Still bit-identical — for any core
    # count.
    grid = net.compile(ExecutionPlan(mode=Mode.MEGAKERNEL, cores=2))
    gresult = grid.run()
    gstats = grid.stats()
    assert np.array_equal(np.asarray(grid.collect("sink")), out)
    print(f"grid x2: partitions {gstats.partition_actors}, "
          f"{int(gresult.sweeps)} rounds, "
          f"{gstats.shared_scratch_bytes} B shared rings+semaphores "
          f"({gstats.cut_objective} cut), per-core cursor rows "
          f"{gstats.core_cursor_rows} + {len(gstats.shared_fifos)} shared")

    # Observability: trace=True records every firing attempt (actor,
    # sweep, fired/skipped, per-channel occupancy) into a device-side
    # ring — bit-identical results, and the decoded Trace exports
    # Chrome trace-event JSON for https://ui.perfetto.dev plus a
    # measured Profile that can drive the partition cut.
    import tempfile
    traced = net.compile(ExecutionPlan(mode="dynamic", trace=True))
    tresult = traced.run()
    trace = tresult.trace
    assert trace.firing_counts() == {k: int(v)
                                     for k, v in tresult.fire_counts.items()}
    with tempfile.NamedTemporaryFile(suffix=".trace.json",
                                     delete=False) as f:
        trace.to_perfetto(f.name)
    prof = trace.profile()
    print(f"trace: {trace.n_events} events ({trace.dropped} dropped), "
          f"perfetto JSON -> {f.name}")
    print("  measured cut weights:",
          {k: v for k, v in sorted(prof.as_cut_weights()['actors'].items())})
    pgrid = net.compile(ExecutionPlan(mode=Mode.MEGAKERNEL, cores=2,
                                      cut_objective="profile", profile=prof))
    assert np.array_equal(np.asarray(pgrid.collect("sink", pgrid.run().state)),
                          out)
    print(f"  profile-driven grid x2 cut: {pgrid.stats().partition_actors} "
          f"(still bit-identical)")

    # Note on donation: ExecutionPlan.donate defaults to "auto" — donate
    # only when the ring-buffered bytes are small enough that copy
    # elision wins (full-size motion detection measured 1.7x SLOWER
    # donated; EXPERIMENTS.md §Executor perf).  Pass donate=True/False to
    # override per run.

    # Multi-device sharding: ExecutionPlan(devices=k) splits the firing
    # table across a 1-D mesh and lowers the crossing channels to
    # collective exchanges at each sweep barrier — bit-identical states
    # and fire counts at any k.  It needs two visible devices: a
    # multi-chip host, or a CPU run started under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8.
    if jax.device_count() >= 2:
        sharded = net.compile(ExecutionPlan(mode="dynamic", devices=2))
        sresult = sharded.run()
        assert np.array_equal(
            np.asarray(sharded.collect("sink", sresult.state)), out)
        sstats = sharded.stats()
        print(f"sharded x{sstats.devices}: "
              f"{int(sresult.sweeps)} barrier rounds, "
              f"{sstats.collective_bytes_per_sweep} B/round collective, "
              f"partition {sstats.device_partition_actors} "
              "(still bit-identical)")
    else:
        print(f"sharded x2: skipped — {jax.device_count()} "
              f"{jax.default_backend()} device visible, the demo needs 2 "
              "(on a CPU host, rerun under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

if __name__ == "__main__":
    main()
