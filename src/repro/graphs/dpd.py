"""The Dynamic Predistortion application — paper §4.2, Fig. 5.

Configuration (C) actor periodically reconfigures which of the 10 parallel
Poly (P) branches (nonlinear basis + 10-tap complex FIR) are active; the
Adder (A) sums the active branches.  The number of active filters changes
arbitrarily between 2 and 10 at run time — dynamic data rates that CSDF
cannot model (paper §4.2) and that DAL cannot put on the accelerator.

Wiring (22 complex data channels + 11 control channels):

    source --f_in--> fork --b_k--> poly_k --y_k--> adder --f_out--> sink
    config --c_fork--> fork, --c_k--> poly_k, --c_add--> adder

Complex samples are carried as (2, L) f32 tokens (re/im planes) instead of
the paper's separate re/im channel pairs — identical bytes, half the graph
clutter (DESIGN.md §8).  Token = 32 768 complex samples (256 KB) so that
Eq. 1 over the 22 data channels reproduces Table 1's 11.5 MB, and the
reconfiguration period of 65 536 samples = a new control value every 2
firings (paper §4.2).

The token rate of the dynamic part is 1 — the paper's own restriction
(§5: ports have at most two rates {0, r}; arbitrary run-time data paths
need r=1 to avoid deadlock).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Network, NetworkBuilder, dynamic_actor, static_actor
from repro.core.actor import apply_rate_gate
from repro.kernels.dyn_fir import N_BRANCHES, N_TAPS
from repro.kernels.dyn_fir.ops import dpd_branch

BLOCK_L = 32768                 # complex samples per token (256 KB)
RECONF_PERIOD_SAMPLES = 65536   # paper §4.2
RECONF_PERIOD_FIRINGS = RECONF_PERIOD_SAMPLES // BLOCK_L


def _branch_on(k: int, tok: jax.Array) -> jax.Array:
    """0/1 enable of branch ``k`` given the configuration token.

    One shared predicate for every port the configuration value drives
    (fork.b_k, poly_k.in/out, adder.y_k): identical control *expressions*
    fed by provably-equal control tokens are what lets
    ``NetworkBuilder.build`` derive ``matched_rates`` — and thus transient-
    channel register allocation — instead of taking it on declaration.
    """
    return (jnp.int32(k) < tok[0]).astype(jnp.int32)


def default_active_schedule(n_firings: int, seed: int = 0,
                            lo: int = 2, hi: int = N_BRANCHES) -> np.ndarray:
    """Number of active filters per firing: changes every RECONF period,
    arbitrary in [lo, hi] (paper: 2..10, externally defined)."""
    rng = np.random.default_rng(seed)
    n_periods = -(-n_firings // RECONF_PERIOD_FIRINGS)
    per = rng.integers(lo, hi + 1, n_periods)
    return np.repeat(per, RECONF_PERIOD_FIRINGS)[:n_firings].astype(np.int32)


def branch_taps(k: int) -> np.ndarray:
    """Poly branch ``k``'s (2, N_TAPS) f32 complex taps (re, im), fixed
    per branch so runs and the oracle agree."""
    return np.random.default_rng(100 + k).normal(
        scale=0.3, size=(2, N_TAPS)).astype(np.float32)


def dpd_oracle(signal: np.ndarray, active_schedule: np.ndarray,
               block_l: int = BLOCK_L) -> np.ndarray:
    """Plain NumPy float32 reference of the network's sink output.

    ``signal``: (2, n_firings * block_l) re/im planes; firing ``f`` sums
    branches ``k < active_schedule[f]``, each ``x * |x|^(2k)`` through
    its 10-tap complex FIR with a 9-sample history that advances only
    when the branch runs.
    """
    L = block_l
    sig = np.asarray(signal, np.float32)
    hist = [np.zeros((2, N_TAPS - 1), np.float32) for _ in range(N_BRANCHES)]
    out = np.zeros_like(sig)
    for f, n_active in enumerate(np.asarray(active_schedule)):
        win = sig[:, f * L:(f + 1) * L]
        for k in range(int(n_active)):
            x = np.concatenate([hist[k], win], axis=1)
            hist[k] = x[:, -(N_TAPS - 1):]
            xr, xi = x
            scale = (xr * xr + xi * xi) ** k
            br, bi = xr * scale, xi * scale
            hr, hi = branch_taps(k)
            yr = np.zeros(L, np.float32)
            yi = np.zeros(L, np.float32)
            for t in range(N_TAPS):
                sr = br[N_TAPS - 1 - t:N_TAPS - 1 - t + L]
                si = bi[N_TAPS - 1 - t:N_TAPS - 1 - t + L]
                yr = yr + hr[t] * sr - hi[t] * si
                yi = yi + hr[t] * si + hi[t] * sr
            out[0, f * L:(f + 1) * L] += yr
            out[1, f * L:(f + 1) * L] += yi
    return out


def build_dpd(n_firings: int,
              active_schedule: Optional[np.ndarray] = None,
              block_l: int = BLOCK_L,
              n_branches: int = N_BRANCHES,
              signal: Optional[jax.Array] = None,
              fir_impl: str = "xla",
              static_all_active: bool = False) -> Network:
    """Build the DPD network.

    ``static_all_active=True`` builds the *static* variant (every branch
    always on, no control ports) — the DAL-compatible baseline used for
    the Table 4 comparison and the 5x measurement.
    """
    L = block_l
    tok = (2, L)
    if active_schedule is None:
        active_schedule = default_active_schedule(n_firings)
    sched = jnp.asarray(active_schedule, jnp.int32)
    branch_names = [f"poly{k}" for k in range(n_branches)]

    # ---------------------------------------------------------------- #
    # Source / sink.
    # ---------------------------------------------------------------- #
    def src_fire(state, inputs, rates):
        data, idx = state
        win = jax.lax.dynamic_slice_in_dim(data, idx * L, L, axis=1)
        return (data, idx + 1), {"out": win[None]}

    def src_init():
        data = (signal if signal is not None
                else jnp.zeros((2, n_firings * L), jnp.float32))
        return (jnp.asarray(data, jnp.float32), jnp.int32(0))

    source = static_actor("source", (), ("out",), src_fire, init=src_init,
                          ready=lambda st: st[1] < n_firings)

    def sink_fire(state, inputs, rates):
        data, idx = state
        data = jax.lax.dynamic_update_slice_in_dim(
            data, inputs["in"][0], idx * L, axis=1)
        return (data, idx + 1), {}

    sink = static_actor("sink", ("in",), (), sink_fire,
                        init=lambda: (jnp.zeros((2, n_firings * L), jnp.float32),
                                      jnp.int32(0)),
                        finish=lambda st: st[0])

    # ---------------------------------------------------------------- #
    # Configuration actor: emits the active-count token to 12 controls.
    # ---------------------------------------------------------------- #
    ctrl_ports = ["c_fork", "c_add"] + [f"c{k}" for k in range(n_branches)]

    def config_fire(state, inputs, rates):
        idx = state
        n_active = sched[jnp.clip(idx, 0, sched.shape[0] - 1)]
        tok_out = n_active.reshape(1, 1)
        return idx + 1, {p: tok_out for p in ctrl_ports}

    config = static_actor("config", (), tuple(ctrl_ports), config_fire,
                          init=lambda: jnp.int32(0),
                          ready=lambda st: st < n_firings)

    # ---------------------------------------------------------------- #
    # Fork: broadcast the input window to enabled branches only.
    # ---------------------------------------------------------------- #
    fork_outs = tuple(f"b{k}" for k in range(n_branches))

    def fork_control(tok):
        d = {"in": jnp.int32(1)}
        for k in range(n_branches):
            d[f"b{k}"] = _branch_on(k, tok)
        return d

    def fork_fire(state, inputs, rates):
        return state, {p: inputs["in"] for p in fork_outs}

    if static_all_active:
        fork = static_actor("fork", ("in",), fork_outs, fork_fire)
    else:
        fork = dynamic_actor("fork", "c", fork_control, ("in",), fork_outs,
                             fork_fire)

    # ---------------------------------------------------------------- #
    # Poly branches: basis + 10-tap complex FIR, 9-sample history state.
    # ---------------------------------------------------------------- #
    def make_poly(k: int):
        order = k + 1

        def init():
            hist = jnp.zeros((2, N_TAPS - 1), jnp.float32)
            return (hist, jnp.asarray(branch_taps(k)))

        def fire(state, inputs, rates):
            hist, taps = state
            win = inputs["in"][0]                      # (2, L)
            x = jnp.concatenate([hist, win], axis=1)   # (2, L + T - 1)
            yr, yi = dpd_branch(x[0], x[1], taps[0], taps[1], order=order,
                                impl=fir_impl)
            new_hist = x[:, -(N_TAPS - 1):]
            return (new_hist, taps), {"out": jnp.stack([yr, yi])[None]}

        def control(tok):
            on = _branch_on(k, tok)
            return {"in": on, "out": on}

        flops = 2 * L * (4 * N_TAPS + 2 * order)  # complex MACs + basis
        if static_all_active:
            return static_actor(f"poly{k}", ("in",), ("out",), fire, init=init,
                                cost_flops=flops)
        return dynamic_actor(f"poly{k}", "c", control, ("in",), ("out",), fire,
                             init=init, cost_flops=flops)

    polys = [make_poly(k) for k in range(n_branches)]

    # ---------------------------------------------------------------- #
    # Adder: sum of enabled branch outputs.
    # ---------------------------------------------------------------- #
    add_ins = tuple(f"y{k}" for k in range(n_branches))

    def adder_fire(state, inputs, rates):
        acc = jnp.zeros((1, 2, L), jnp.float32)
        for k in range(n_branches):
            # Disabled windows hold stale data — gate by the rate flag
            # (folded away at trace time in the static-rewrite build).
            term = apply_rate_gate(rates[f"y{k}"], inputs[f"y{k}"])
            if term is not None:
                acc = acc + term
        return state, {"out": acc}

    def adder_control(tok):
        d = {"out": jnp.int32(1)}
        for k in range(n_branches):
            d[f"y{k}"] = _branch_on(k, tok)
        return d

    if static_all_active:
        adder = static_actor("adder", add_ins, ("out",), adder_fire)
    else:
        adder = dynamic_actor("adder", "c", adder_control, add_ins, ("out",),
                              adder_fire)

    # ---------------------------------------------------------------- #
    # Wiring (declarative; Eq. 1 capacities derived per channel).
    # ---------------------------------------------------------------- #
    # In the dynamic build, every data channel's two ports are driven by
    # the same configuration value (fork.b_k, poly_k and adder.y_k all
    # evaluate `_branch_on(k, tok)`; f_in and f_out are unconditionally
    # enabled), so builder derivation proves them matched-rate transient
    # channels: the specialized static executor register-allocates them
    # instead of paying the masked ring writes' read-modify-write on
    # 256 KB windows.  The static rewrite has static actors at both ends,
    # where the buffered static-offset path is already optimal (the
    # contiguous ring write doubles as the materialization point between
    # actor bodies) — derivation never marks static-static channels.
    b = NetworkBuilder()
    if not static_all_active:
        b.actor(config)
    b.actors(source, fork, *polys, adder, sink)
    b.connect("source.out", "fork.in", token_shape=tok, name="f_in")
    b.connect("adder.out", "sink.in", token_shape=tok, name="f_out")
    for k in range(n_branches):
        b.connect(f"fork.b{k}", f"poly{k}.in", token_shape=tok, name=f"f_b{k}")
        b.connect(f"poly{k}.out", f"adder.y{k}", token_shape=tok,
                  name=f"f_y{k}")
    if not static_all_active:
        b.connect("config.c_fork", "fork.c", name="f_c_fork")
        b.connect("config.c_add", "adder.c", name="f_c_add")
        for k in range(n_branches):
            b.connect(f"config.c{k}", f"poly{k}.c", name=f"f_c{k}")
    return b.build()


def bench_workload(n_firings: int, block_l: int = BLOCK_L, seed: int = 1,
                   **build_kw) -> Network:
    """DPD network staged with a reproducible random signal.

    Shared by benchmarks/bench_executors.py and tests/test_perf_smoke.py so
    the measured workload (and its Msamples accounting: ``n_firings *
    block_l`` complex samples end to end) is defined in one place.
    Delegates the signal staging to ``repro.graphs.factories.make_dpd``
    (single source of truth), keeping this module's historical
    ``default_active_schedule`` reconfiguration pattern.
    """
    from repro.graphs.factories import make_dpd
    build_kw.setdefault("active_schedule", default_active_schedule(n_firings))
    net, _ = make_dpd(n_firings, block_l=block_l, seed=seed, **build_kw)
    return net
