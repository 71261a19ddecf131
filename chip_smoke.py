#!/usr/bin/env python3
"""On-chip smoke test: the actor-network runtime's main path on one TPU.

Drives ``NetworkBuilder`` -> ``ExecutionPlan`` -> ``Program`` /
``ActorEngine`` through the public entry points, at the paper's sizes:

* ``dpd``: the DPD graph (paper §4.2) at 32768 complex samples per token
  and 10 Poly branches, 64 firings, under ``static`` and ``dynamic`` with
  ``fir_impl="xla"`` and ``"pallas"``, plus the all-active static rewrite
  (the paper's 5x baseline).  Sink vs :func:`repro.graphs.dpd.dpd_oracle`.
* ``md``: motion detection (paper §4.1) on 96 QVGA u8 frames at rate 4,
  ``static`` and ``dynamic``, ``gauss_impl="xla"`` and ``"pallas"``.  Sink
  vs :func:`repro.graphs.motion_detection.md_oracle`, exactly.
* ``stream``: DPD streamed through ``Program.stream`` in 4 chunks under a
  ``dynamic`` plan with every actor but source and sink accelerated; the
  output must equal the whole-network ``run()`` output.
* ``serve``: ``ActorEngine`` (default dynamic plan) on h2o-danube-3-4b at
  its published widths with bf16 weights from the seed, 8 requests with
  Poisson arrivals; greedy tokens must equal the legacy ``Engine``'s.

Every program runs twice from a fresh ``init_state()`` and must give the
same output both times.  ``compile_s`` is the first call's time minus the
second's (the second call is ``run_s``).

``--chips 4`` runs only ``shard``: DPD at the sizes above under
``ExecutionPlan(mode="dynamic", devices=4)`` against ``devices=1``; states
and fire counts must be bit-identical.

One line per phase, then the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits 1 and prints no result.  ``--rehearse``
runs every phase at a tiny size on whatever backend JAX has (CPU, with
Pallas in interpret mode), to check the script without a chip:

    python chip_smoke.py                       # one TPU chip
    python chip_smoke.py --chips 4             # four chips, shard only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Tolerance of the DPD sink against the float32 NumPy oracle (the one
#: ``tests/test_graphs_paper.py`` uses).
DPD_RTOL = DPD_ATOL = 6e-4
#: Motion detection is compared exactly: every Gauss partial sum is a
#: multiple of 1/256 below 2^16, exact in float32 in any order.
MD_MAX_MISMATCHED_PIXELS = 0

FULL = dict(dpd_block_l=32768, dpd_firings=64, md_hw=(240, 320),
            md_frames=96, md_rate=4, arch="h2o-danube-3-4b",
            serve=dict(batch_size=4, max_prompt=128, max_new=32),
            n_requests=8)
TINY = dict(dpd_block_l=1024, dpd_firings=8, md_hw=(48, 64),
            md_frames=8, md_rate=4, arch="h2o-danube-3-4b",
            serve=dict(batch_size=4, max_prompt=16, max_new=8),
            n_requests=8)


class CheckFailed(AssertionError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _timed_twice(fn):
    """``fn()`` twice; returns (first output, second output, compile_s,
    run_s).  ``fn`` must return host (NumPy) data, so it has synced."""
    t0 = time.perf_counter()
    a = fn()
    t1 = time.perf_counter()
    b = fn()
    t2 = time.perf_counter()
    return a, b, max(t1 - t0 - (t2 - t1), 0.0), t2 - t1


def _dpd_signal(seed: int, n_firings: int, block_l: int):
    """Complex Gaussian baseband normalized to unit peak amplitude, as a
    DPD sees its input scaled to the amplifier's saturation level."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sig = rng.normal(size=(2, n_firings * block_l))
    return (sig / np.sqrt((sig ** 2).sum(0)).max()).astype(np.float32)


def _run_sink(prog):
    import numpy as np
    res = prog.run()
    return np.asarray(prog.collect("sink", res.state)), res


def phase_dpd(sz, seed, report, cache):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ExecutionPlan
    from repro.graphs.dpd import (N_BRANCHES, build_dpd,
                                  default_active_schedule, dpd_oracle)
    L, nf = sz["dpd_block_l"], sz["dpd_firings"]
    sig = _dpd_signal(seed, nf, L)
    sched = default_active_schedule(nf, seed=seed)
    want = {False: dpd_oracle(sig, sched, L),
            True: dpd_oracle(sig, np.full(nf, N_BRANCHES), L)}
    cache.update(sig=sig, sched=sched)
    runs = [(mode, impl, False) for mode in ("static", "dynamic")
            for impl in ("xla", "pallas")]
    runs += [("static", impl, True) for impl in ("xla", "pallas")]
    for mode, impl, all_active in runs:
        name = f"dpd/{mode}/{impl}" + ("/all_active" if all_active else "")
        with report(name) as line:
            net = build_dpd(nf, active_schedule=sched, block_l=L,
                            signal=jnp.asarray(sig), fir_impl=impl,
                            static_all_active=all_active)
            prog = net.compile(ExecutionPlan(
                mode=mode, n_iterations=nf if mode == "static" else None))
            (a, ra), (b, _), c_s, r_s = _timed_twice(lambda: _run_sink(prog))
            err = float(np.abs(a - want[all_active]).max())
            line.update(compile_s=c_s, run_s=r_s,
                        msamples_per_s=nf * L / r_s / 1e6,
                        max_abs_err=err, repeat_identical=np.array_equal(a, b))
            _check(np.array_equal(a, b), "two runs from init_state differ")
            _check(np.allclose(a, want[all_active], rtol=DPD_RTOL,
                               atol=DPD_ATOL),
                   f"sink vs oracle: max abs err {err}")
            if mode == "dynamic":
                _check(int(ra.fire_counts["config"]) == nf,
                       f"config fired {int(ra.fire_counts['config'])}")
                if impl == "xla":
                    cache["dynamic_out"] = a


def phase_md(sz, seed, report):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ExecutionPlan
    from repro.graphs.motion_detection import build_motion_detection, md_oracle
    (H, W), nf, rate = sz["md_hw"], sz["md_frames"], sz["md_rate"]
    video = np.random.default_rng(seed).integers(
        0, 256, (nf, H, W)).astype(np.uint8)
    want = md_oracle(video)
    for mode in ("static", "dynamic"):
        for impl in ("xla", "pallas"):
            with report(f"md/{mode}/{impl}") as line:
                net = build_motion_detection(nf, rate=rate, frame_hw=(H, W),
                                             video=jnp.asarray(video),
                                             gauss_impl=impl)
                prog = net.compile(ExecutionPlan(
                    mode=mode,
                    n_iterations=nf // rate if mode == "static" else None))
                (a, _), (b, _), c_s, r_s = _timed_twice(
                    lambda: _run_sink(prog))
                bad = int((a != want).sum())
                line.update(compile_s=c_s, run_s=r_s, fps=nf / r_s,
                            mismatched_pixels=bad,
                            repeat_identical=np.array_equal(a, b))
                _check(np.array_equal(a, b), "two runs from init_state differ")
                _check(bad <= MD_MAX_MISMATCHED_PIXELS,
                       f"{bad} pixels differ from the oracle")


def phase_stream(sz, report, cache, rehearse):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ExecutionPlan
    from repro.graphs.dpd import build_dpd
    L, nf = sz["dpd_block_l"], sz["dpd_firings"]
    n_chunks = 4
    with report("stream/dpd/dynamic") as line:
        if "dynamic_out" not in cache:
            raise CheckFailed("needs the dpd/dynamic/xla output")
        sig, want = cache["sig"], cache["dynamic_out"]
        net = build_dpd(nf, active_schedule=cache["sched"], block_l=L,
                        signal=jnp.asarray(sig))
        accel = tuple(n for n in net.actors if n not in ("source", "sink"))
        prog = net.compile(ExecutionPlan(mode="dynamic",
                                         n_iterations=nf // n_chunks,
                                         accelerated=accel))
        wins = sig.reshape(2, nf, L).transpose(1, 0, 2)[:, None]

        def stream():
            outs = prog.stream({"f_in": wins})
            return np.concatenate(list(np.asarray(outs["f_out"])[:, 0]),
                                  axis=1)

        a, b, c_s, r_s = _timed_twice(stream)
        line.update(compile_s=c_s, run_s=r_s, chunks=n_chunks,
                    max_abs_diff_vs_run=float(np.abs(a - want).max()),
                    repeat_identical=np.array_equal(a, b))
        _check(np.array_equal(a, b), "two streams differ")
        # Exact on the chip.  The CPU backend fuses the chunk program and
        # the whole-network program differently and contracts different
        # multiply-adds, so a rehearsal allows the 1e-6 that
        # tests/test_program_api.py allows for the same comparison.
        tol = 1e-6 if rehearse else 0.0
        _check(np.allclose(a, want, rtol=tol, atol=tol),
               "stream output != run() output")


def phase_serve(sz, seed, report, rehearse):
    import jax
    import numpy as np
    from repro.configs import get_config, smoke_config
    from repro.graphs.serving import poisson_trace
    from repro.models import init_params
    from repro.serve import ActorEngine, Engine, Request, ServeConfig
    arch = sz["arch"]
    cfg = smoke_config(arch) if rehearse else get_config(arch)
    scfg = ServeConfig(**sz["serve"])
    with report(f"serve/{arch}/actor_engine") as line:
        params = jax.jit(lambda k: init_params(k, cfg))(
            jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        n = sz["n_requests"]
        requests = [
            Request(prompt=rng.integers(0, cfg.vocab, int(p)).astype(np.int32),
                    max_new=int(m))
            for p, m in zip(rng.integers(1, scfg.max_prompt + 1, n),
                            rng.integers(1, scfg.max_new + 1, n))]
        arrivals = poisson_trace(n, rate=0.5, seed=seed)
        legacy = Engine(cfg, params, scfg)
        ref, _, _, legacy_s = _timed_twice(lambda: legacy.generate(requests))
        actor = ActorEngine(cfg, params, scfg)
        t0 = time.perf_counter()
        out = actor.generate(requests, arrivals=arrivals)
        first_s = time.perf_counter() - t0
        prog = actor.last_program
        t0 = time.perf_counter()
        jax.block_until_ready(prog.run().state)
        run_s = time.perf_counter() - t0
        n_tok = sum(len(r.tokens) for r in out)
        same = all(np.array_equal(a.tokens, b.tokens)
                   for a, b in zip(ref, out))
        line.update(params_gb=sum(x.nbytes for x in jax.tree.leaves(params))
                    / 1e9, compile_s=max(first_s - run_s, 0.0), run_s=run_s,
                    tokens=n_tok, tokens_per_s=n_tok / run_s,
                    sweeps=actor.last_sweeps, legacy_run_s=legacy_s,
                    tokens_identical=same)
        _check(same, "ActorEngine tokens != Engine tokens")
        _check(all(len(r.tokens) == q.max_new
                   for r, q in zip(out, requests)),
               "a request produced fewer tokens than its budget")


def phase_shard(sz, seed, report, k):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ExecutionPlan
    from repro.graphs.dpd import build_dpd, default_active_schedule, dpd_oracle
    from repro.graphs.factories import states_identical
    L, nf = sz["dpd_block_l"], sz["dpd_firings"]
    sig = _dpd_signal(seed, nf, L)
    sched = default_active_schedule(nf, seed=seed)
    with report(f"shard/dpd/dynamic/devices{k}") as line:
        net = build_dpd(nf, active_schedule=sched, block_l=L,
                        signal=jnp.asarray(sig))
        progs = {d: net.compile(ExecutionPlan(mode="dynamic", devices=d))
                 for d in (1, k)}
        res, secs = {}, {}
        for d, prog in progs.items():
            def once(prog=prog):
                r = prog.run()
                return np.asarray(prog.collect("sink", r.state)), r
            (a, ra), (b, _), c_s, r_s = _timed_twice(once)
            _check(np.array_equal(a, b), f"devices={d}: two runs differ")
            res[d], secs[d] = (a, ra), (c_s, r_s)
        (a1, r1), (ak, rk) = res[1], res[k]
        counts = {n: (int(r1.fire_counts[n]), int(rk.fire_counts[n]))
                  for n in r1.fire_counts}
        identical = states_identical(r1.state, rk.state)
        line.update(compile_s=secs[k][0], run_s=secs[k][1],
                    run_s_devices1=secs[1][1],
                    barrier_rounds=int(rk.sweeps),
                    sweeps_devices1=int(r1.sweeps),
                    collective_bytes_per_round=progs[k].stats()
                    .collective_bytes_per_sweep,
                    states_identical=identical,
                    fire_counts_identical=all(x == y
                                              for x, y in counts.values()))
        _check(identical, "devices=k state != devices=1 state")
        _check(all(x == y for x, y in counts.values()),
               f"fire counts differ: {counts}")
        _check(np.allclose(a1, dpd_oracle(sig, sched, L), rtol=DPD_RTOL,
                           atol=DPD_ATOL), "devices=1 sink vs oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend (no chip needed)")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 1
    sz = TINY if args.rehearse else FULL
    failed = []

    class report:
        """Context manager: one ``phase ...`` line per phase, PASS/FAIL."""

        def __init__(self, name):
            self.name, self.line = name, {}

        def __enter__(self):
            return self.line

        def __exit__(self, exc_type, exc, tb):
            ok = exc_type is None
            if not ok:
                failed.append(self.name)
                self.line["error"] = f"{exc_type.__name__}: {exc}"[:400]
                if not isinstance(exc, CheckFailed):
                    traceback.print_exception(exc_type, exc, tb,
                                              file=sys.stderr)
            fields = " ".join(
                f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in self.line.items())
            print(f"phase {self.name} device={dev.device_kind!r} "
                  f"check={'PASS' if ok else 'FAIL'} {fields}", flush=True)
            return exc_type is None or issubclass(exc_type, Exception)

    if args.chips > 1:
        phase_shard(sz, args.seed, report, args.chips)
    else:
        cache = {}
        phase_dpd(sz, args.seed, report, cache)
        phase_md(sz, args.seed, report)
        phase_stream(sz, report, cache, args.rehearse)
        phase_serve(sz, args.seed, report, args.rehearse)

    result = {"ok": not failed,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
    print(json.dumps(result))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
