"""Continuous-batching LM serving as a dynamic-rate actor network.

The serving loop is the paper's adaptive-application pattern (§2.2/§4.3)
applied to the ROADMAP's top new direction: requests arrive mid-flight,
decode lengths are data-dependent, and a slot that hits EOS (or its
budget) is a **rate-0 firing** whose freed slot is re-admitted on the
next sweep.  The graph::

            +--------------------- fb (delay=1) ------------------+
            v                                                     |
      admission ---- table ------------------------------> merge -+
       (static) ---- x -----> gate ---- xa ----> decode --- y ---^
            |                  |      (dynamic: skips the model
            |                  |       when no slot is active)
            |                  +---- fina ----> retire (dynamic sink)
            +-- c_gate / c_dec / c_merge / c_ret  (one control token
                broadcast to every dynamic actor, MoC rate 1)

    * **admission** (static, the loop head): consumes the slot-table
      feedback, extracts the slots the previous step finished (their
      freed rows become admissible again — the re-admission loop),
      admits 0..k waiting arrivals into free slots (data-dependent
      production, realized as a masked fixed-capacity window plus the
      control token's admit count, exactly the MoE-router idiom), and
      broadcasts ONE control token ``[n_active, n_finished, n_admitted]``
      to every dynamic actor.  Its ``ready`` predicate retires the
      network once every request has been collected.
    * **gate** (dynamic): forwards the slot table to the decode actor and
      the finished rows to the retire actor — but only when the matching
      count is non-zero.  The gate exists so *both* endpoints of the
      ``xa``/``fina`` channels are enabled by the same control value:
      a producer that writes while its consumer skips would drift the
      FIFO occupancy and break window pairing (the MoC hazard the
      matched-rates derivation exists to rule out).
    * **decode** (dynamic): one ``decode_step`` per firing over the B
      slots, plus a ``lax.cond``-gated ``prefill`` on firings that admit
      new requests; KV caches are the actor state.  When ``n_active ==
      0`` every regular port is rate 0 and the whole body is skipped —
      the EOS/idle rate-0 firing the paper's 5x comes from (it still
      counts in ``fire_counts``: the control token is consumed).
    * **merge** (dynamic): folds the decoded tokens back into the slot
      table (append, advance pos, detect EOS/budget exhaustion) and
      writes the feedback token.  The per-slot decode state rides this
      delay-token feedback FIFO — the KV/decode loop-carry the legacy
      engine kept implicit.
    * **retire** (dynamic sink): collects finished sequences (tokens,
      lengths, step latency) keyed by request id; fires rate-0 when the
      step finished nothing.

    Every delay-free channel between a static producer and a dynamic
    consumer (or between two dynamic actors) is *provably* matched: the
    shared enable predicates below trace to identical jaxprs and the
    control channels all feed from one broadcast token
    (``derive_matched_rates``), so ``build(check_bounds=True)`` proves
    every channel ``balanced`` — the PRUNE-style decidability the ISSUE
    asks for, declared via ``rate_bounds`` for the data-dependent ports.

Bit-identity contract: per-request greedy tokens equal the legacy
``repro.serve.Engine`` output token-for-token.  Both engines call the
same ``prefill``/``decode_step`` at the same shapes — (B, P) prompts,
(B, 1) decode tokens — and dense-model rows are computed independently
of their batchmates, so *when* a request is admitted cannot change its
tokens.  (MoE configs couple rows through expert capacity; the identity
oracle holds for dense families only.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import Network, NetworkBuilder, dynamic_actor, static_actor
from repro.models import lm as lm_mod

PyTree = Any

# Slot-table header columns (one row per slot, i32 everywhere; tokens,
# positions and counters are all ints).  After the header: P prompt
# columns (left-padded), then max_new generated-token columns.
C_ACTIVE = 0    # slot holds a live request
C_REQ = 1       # request id (index into the staged request slabs)
C_POS = 2       # next decode_step position (P + produced - 1)
C_PROD = 3      # tokens produced so far (includes the prefill token)
C_BUDGET = 4    # per-request max_new
C_FIN = 5       # finished last step (freed + collected next firing)
C_LAST = 6      # last produced token (decode_step input)
C_NEW = 7       # admitted this firing (decode runs prefill for the row)
C_LAT = 8       # scratch: completion latency in steps (finish extraction)
C_STATUS = 9    # retirement status code (STATUS_*)
C_DEADLINE = 10  # absolute retire-by step (NO_DEADLINE = unconstrained)
C_AGE = 11      # decode steps survived in a slot (admission resets to 0)
HEADER = 12

# Retirement status codes carried in C_STATUS and collected per request
# by the retire sink.
STATUS_OK = 0        # finished normally (EOS or budget)
STATUS_TIMEOUT = 1   # deadline expired (in flight or while waiting)
STATUS_SHED = 2      # shed by admission under queue overflow
STATUS_FAULT = 3     # quarantined after a guarded-run fault

# Every slot-table value — token ids, positions, counters, deadlines —
# is a non-negative i32 below 2**30; the channels declare this as their
# guard domain, so a poisoned row trips the DOMAIN fault bit on write.
NO_DEADLINE = 2**30 - 1
SLOT_DOMAIN = (0.0, float(2**30))


@dataclasses.dataclass(frozen=True)
class ServingWorkload:
    """The staged request set of one serving run (host-fed arrival queue)."""

    prompts: np.ndarray       # (R, P) i32, left-padded
    prompt_lens: np.ndarray   # (R,) i32
    budgets: np.ndarray       # (R,) i32 per-request max_new (>= 1)
    arrivals: np.ndarray      # (R,) i32 arrival step, ascending
    # Absolute retire-by step per request; None = no deadlines
    # (every entry NO_DEADLINE).
    deadlines: Optional[np.ndarray] = None


# --------------------------------------------------------------------- #
# Shared enable predicates: every channel endpoint gated by the same
# expression of the same broadcast control token, so the matched-rates
# derivation proves the channels balanced (identical canonical jaxprs +
# feeder ports shown equal by tracing admission's fire).
# --------------------------------------------------------------------- #
def _on_active(tok: jax.Array) -> jax.Array:
    return (tok[0] > 0).astype(jnp.int32)


def _on_fin(tok: jax.Array) -> jax.Array:
    return (tok[1] > 0).astype(jnp.int32)


def _batch_axes(template_small, template_big) -> List[int]:
    """Per-leaf batch axis of a cache pytree, by shape comparison between
    two eval_shape templates that differ only in batch size."""
    ls, lb = jax.tree.leaves(template_small), jax.tree.leaves(template_big)
    axes: List[int] = []
    for s, b in zip(ls, lb):
        diff = [i for i, (x, y) in enumerate(zip(s.shape, b.shape)) if x != y]
        if len(diff) != 1:
            raise ValueError(
                "serving: cannot locate the batch axis of a cache leaf "
                f"(shape {s.shape} vs {b.shape}); per-slot cache merging "
                "needs exactly one batch-dependent axis per leaf")
        axes.append(diff[0])
    return axes


def _select_rows(mask: jax.Array, axes: List[int], new: PyTree,
                 old: PyTree) -> PyTree:
    """Per-row select over a cache pytree: rows where ``mask`` take
    ``new``, others keep ``old`` (batch axis varies per leaf)."""
    flat_new, treedef = jax.tree.flatten(new)
    flat_old = jax.tree.leaves(old)
    out = []
    for n, o, ax in zip(flat_new, flat_old, axes):
        shape = [1] * n.ndim
        shape[ax] = mask.shape[0]
        out.append(jnp.where(mask.reshape(shape), n, o))
    return jax.tree.unflatten(treedef, out)


def left_pad_prompts(prompts: List[np.ndarray], max_prompt: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Left-pad prompts into an (R, P) slab exactly as ``Engine._pad_batch``
    does (prompts end together), returning (slab, lens)."""
    R, P = len(prompts), max_prompt
    slab = np.zeros((R, P), np.int32)
    lens = np.zeros((R,), np.int32)
    for i, p in enumerate(prompts):
        p = np.asarray(p, np.int32)[-P:]
        slab[i, P - len(p):] = p
        lens[i] = len(p)
    return slab, lens


def poisson_trace(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """Seeded open-loop Poisson arrival trace: ``n`` ascending integer
    arrival steps with exponential inter-arrival gaps of mean ``1/rate``."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(np.int32)


def admission_state(workload: ServingWorkload, *, max_prompt: int,
                    max_new: int) -> Dict[str, jax.Array]:
    """The admission actor's initial state for ``workload``: the staged
    request slabs, which every admission firing reads and passes through
    unchanged, and zeroed queue counters.

    Validates the workload first.  The arrays are new on every call and
    shared with nothing, so a run may donate them; a program built for one
    workload serves another of the same request count from this state."""
    R, P, N = int(workload.prompts.shape[0]), max_prompt, max_new
    if R == 0:
        raise ValueError("serving: empty workload; stage >= 1 request")
    if workload.prompts.shape[1] != P:
        raise ValueError(
            f"serving: prompt slab width {workload.prompts.shape[1]} != "
            f"max_prompt {P}")
    if (workload.budgets < 1).any() or (workload.budgets > N).any():
        raise ValueError(
            f"serving: per-request budgets must be in 1..max_new={N}")
    if (np.diff(workload.arrivals) < 0).any():
        raise ValueError("serving: arrival trace must be ascending")
    deadlines = (np.full((R,), NO_DEADLINE, np.int32)
                 if workload.deadlines is None
                 else np.asarray(workload.deadlines, np.int32))
    if deadlines.shape != (R,):
        raise ValueError(
            f"serving: deadlines shape {deadlines.shape} != ({R},)")
    return {"taken": jnp.zeros((R,), jnp.int32), "t": jnp.int32(0),
            "retired": jnp.int32(0),
            "prompts": jnp.array(workload.prompts, jnp.int32),
            "budgets": jnp.array(workload.budgets, jnp.int32),
            "arrivals": jnp.array(workload.arrivals, jnp.int32),
            "deadlines": jnp.array(deadlines, jnp.int32)}


# --------------------------------------------------------------------- #
# Graph construction.
# --------------------------------------------------------------------- #
def build_serving_network(cfg: ArchConfig, params: PyTree,
                          workload: ServingWorkload, *,
                          batch_size: int, max_prompt: int, max_new: int,
                          eos_id: Optional[int] = None,
                          kernel_impl: str = "xla",
                          queue_depth: Optional[int] = None,
                          check_bounds: bool = True,
                          return_bounds: bool = False) -> Network:
    """Build the admission/gate/decode/merge/retire serving network with
    ``workload`` staged as the host-fed arrival queue, in the admission
    actor's initial state (``admission_state``).

    ``queue_depth`` bounds the waiting queue: arrived requests that would
    queue deeper than ``queue_depth`` behind this firing's admissions are
    shed (``STATUS_SHED``); ``None`` queues without bound.  Requests whose
    deadline passes — waiting or in flight — retire as ``STATUS_TIMEOUT``.
    Both are rate-0 outcomes of the same admission firing: shedding is
    backpressure expressed as a dynamic rate, not an error path.

    ``return_bounds=True`` returns ``(network, BoundsReport)`` so callers
    can pin the per-channel verdicts the build proved."""
    B, P, N = batch_size, max_prompt, max_new
    W = HEADER + P + N
    admission_state(workload, max_prompt=P, max_new=N)    # validates
    R = int(workload.prompts.shape[0])
    if queue_depth is not None and queue_depth < 0:
        raise ValueError(f"serving: queue_depth={queue_depth} must be >= 0")
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    cache_len = P + N
    qd = jnp.int32(B + R if queue_depth is None else queue_depth)

    # -- admission: static loop head -------------------------------------
    # The staged requests ride the actor's state, not this closure: the
    # network depends on the workload only through R, so its program
    # serves any request set of R requests (ActorEngine keeps one per R).
    def admission_init():
        return admission_state(workload, max_prompt=P, max_new=N)

    def admission_fire(st, ins, rates):
        del rates
        t = st["t"]
        prompts, budgets = st["prompts"], st["budgets"]
        arrivals, deadlines = st["arrivals"], st["deadlines"]
        idx = jnp.arange(R, dtype=jnp.int32)
        tbl = ins["fb"][0]
        # In-flight deadline expiry retires the slot exactly like an EOS:
        # FIN=1 with TIMEOUT status, freed and collected this firing.
        expired_slot = (tbl[:, C_ACTIVE] > 0) & (tbl[:, C_DEADLINE] < t)
        tbl = tbl.at[:, C_FIN].set(
            jnp.where(expired_slot, 1, tbl[:, C_FIN]))
        tbl = tbl.at[:, C_STATUS].set(
            jnp.where(expired_slot, STATUS_TIMEOUT, tbl[:, C_STATUS]))
        fin_mask = tbl[:, C_FIN] > 0
        n_fin = jnp.sum(fin_mask.astype(jnp.int32))
        # Completion latency: the finishing token was produced at step
        # t-1; the request waited since its (open-loop) arrival step.
        req = jnp.clip(tbl[:, C_REQ], 0, R - 1)
        lat = (t - 1) - arrivals[req]
        fin_rows = jnp.where(fin_mask[:, None],
                             tbl.at[:, C_LAT].set(lat), 0)
        tbl = jnp.where(fin_mask[:, None], 0, tbl)          # free the slots
        free = tbl[:, C_ACTIVE] == 0

        # The waiting queue is the arrived-but-unserved request set; the
        # ``taken`` vector (not a scalar pointer) lets sheds punch holes
        # in arrival order.
        waiting = (st["taken"] == 0) & (arrivals <= t)
        expired_wait = waiting & (deadlines < t)
        admissible = waiting & ~expired_wait
        adm_rank = jnp.cumsum(admissible.astype(jnp.int32)) - 1
        n_free = jnp.sum(free.astype(jnp.int32))
        k = jnp.minimum(jnp.sum(admissible.astype(jnp.int32)), n_free)
        admit_req = admissible & (adm_rank < k)
        # Queue overflow: admissible requests that would sit deeper than
        # queue_depth behind this firing's k admissions are shed.
        overflow = admissible & (adm_rank >= k + qd)

        # Shed/timeout records ride the free rows of the fin output —
        # at most B - n_fin per firing, the rest stay queued (graceful
        # backlog, never silent drops).
        to_shed = expired_wait | overflow
        shed_status = jnp.where(expired_wait, STATUS_TIMEOUT, STATUS_SHED)
        shed_rank = jnp.cumsum(to_shed.astype(jnp.int32)) - 1
        n_room = B - n_fin
        emit = to_shed & (shed_rank < n_room)
        n_shed = jnp.sum(emit.astype(jnp.int32))
        # Scatter-by-rank: j-th emitted shed lands in the j-th fin-free
        # row (out-of-range indices drop, so ranks >= B are inert).
        req_by_rank = jnp.zeros((B,), jnp.int32).at[
            jnp.where(emit, shed_rank, B)].set(idx, mode="drop")
        room = ~fin_mask
        room_rank = jnp.cumsum(room.astype(jnp.int32)) - 1
        take = room & (room_rank < n_shed)
        sreq = req_by_rank[jnp.clip(room_rank, 0, B - 1)]
        shed_header = jnp.stack([
            jnp.zeros((B,), jnp.int32),           # ACTIVE
            sreq,                                 # REQ
            jnp.zeros((B,), jnp.int32),           # POS
            jnp.zeros((B,), jnp.int32),           # PROD
            budgets[sreq],                        # BUDGET
            jnp.ones((B,), jnp.int32),            # FIN (collected by retire)
            jnp.zeros((B,), jnp.int32),           # LAST
            jnp.zeros((B,), jnp.int32),           # NEW
            t - arrivals[sreq],                   # LAT: age at shed
            shed_status[jnp.clip(sreq, 0, R - 1)],  # STATUS
            deadlines[sreq],                      # DEADLINE
            jnp.zeros((B,), jnp.int32),           # AGE
        ], axis=1)
        shed_rows = jnp.concatenate(
            [shed_header, jnp.zeros((B, P + N), jnp.int32)], axis=1)
        fin_rows = jnp.where(take[:, None], shed_rows, fin_rows)

        # j-th free slot takes the j-th admissible request (arrival
        # order; with no deadlines and unbounded queue this reduces to
        # the PR 7 contiguous-pointer admission bit-for-bit).
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        admit = free & (free_rank < k)
        req_by_arank = jnp.zeros((B,), jnp.int32).at[
            jnp.where(admit_req, adm_rank, B)].set(idx, mode="drop")
        newreq = jnp.clip(req_by_arank[jnp.clip(free_rank, 0, B - 1)],
                          0, R - 1)
        header = jnp.stack([
            jnp.ones((B,), jnp.int32),            # ACTIVE
            newreq,                               # REQ
            jnp.full((B,), P - 1, jnp.int32),     # POS (P + produced - 1)
            jnp.zeros((B,), jnp.int32),           # PROD
            budgets[newreq],                      # BUDGET
            jnp.zeros((B,), jnp.int32),           # FIN
            jnp.zeros((B,), jnp.int32),           # LAST
            jnp.ones((B,), jnp.int32),            # NEW
            jnp.zeros((B,), jnp.int32),           # LAT
            jnp.full((B,), STATUS_OK, jnp.int32),  # STATUS
            deadlines[newreq],                    # DEADLINE
            jnp.zeros((B,), jnp.int32),           # AGE
        ], axis=1)
        new_rows = jnp.concatenate(
            [header, prompts[newreq], jnp.zeros((B, N), jnp.int32)], axis=1)
        tbl = jnp.where(admit[:, None], new_rows, tbl)
        n_active = jnp.sum((tbl[:, C_ACTIVE] > 0).astype(jnp.int32))
        n_out = n_fin + n_shed
        # ONE broadcast token: every control port gets the same traced
        # value, which is what lets the builder prove the feeder ports
        # equal and mark the xa/y/fina channels matched.
        ctl = jnp.stack([n_active, n_out, k])
        taken = jnp.where(admit_req | emit, 1, st["taken"])
        st = {**st, "taken": taken, "t": t + 1,
              "retired": st["retired"] + n_out}
        return st, {"table": tbl, "x": tbl, "fin": fin_rows,
                    "c_gate": ctl, "c_dec": ctl, "c_merge": ctl,
                    "c_ret": ctl}

    admission = static_actor(
        "admission", ["fb"],
        ["table", "x", "fin", "c_gate", "c_dec", "c_merge", "c_ret"],
        admission_fire, init=admission_init,
        ready=lambda st: st["retired"] < R)

    # -- gate: rate-converts admission's static writes to dynamic reads --
    def gate_control(tok):
        return {"x": jnp.int32(1), "fin": jnp.int32(1),
                "xa": _on_active(tok), "fina": _on_fin(tok)}

    def gate_fire(st, ins, rates):
        del rates
        return st, {"xa": ins["x"][0], "fina": ins["fin"][0]}

    gate = dynamic_actor("gate", "c", gate_control, ["x", "fin"],
                         ["xa", "fina"], gate_fire)

    # -- decode: the model actor (KV caches as actor state) --------------
    zero_batch = {"tokens": jnp.zeros((B, P), jnp.int32)}

    def _prefill(batch):
        return lm_mod.prefill(params, cfg, batch, kernel_impl=kernel_impl,
                              max_cache_len=cache_len)

    _, cache_t = jax.eval_shape(_prefill, zero_batch)
    _, cache_t2 = jax.eval_shape(
        lambda b: lm_mod.prefill(params, cfg, b, kernel_impl=kernel_impl,
                                 max_cache_len=cache_len),
        {"tokens": jnp.zeros((B + 1, P), jnp.int32)})
    cache_axes = _batch_axes(cache_t, cache_t2)

    def decode_init():
        return jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), cache_t)

    def decode_control(tok):
        on = _on_active(tok)
        return {"x": on, "y": on}

    def decode_fire(caches, ins, rates):
        del rates
        tbl = ins["x"][0]
        isnew = tbl[:, C_NEW] > 0
        last = tbl[:, C_LAST]
        pos = tbl[:, C_POS]
        prompt_rows = tbl[:, HEADER:HEADER + P]

        def do_prefill(_):
            lg, fresh = _prefill(
                {"tokens": jnp.where(isnew[:, None], prompt_rows, 0)})
            return jnp.argmax(lg, axis=-1).astype(jnp.int32), fresh

        def no_prefill(_):
            return jnp.zeros((B,), jnp.int32), caches

        tok0, fresh = jax.lax.cond(jnp.any(isnew), do_prefill, no_prefill,
                                   None)
        # decode_step runs on the PRE-merge caches: newly prefilled rows
        # must keep their fresh cache rows, not a decode write at a stale
        # position.  Rows are independent, so the continuing rows see
        # exactly the cache content the legacy engine would feed them.
        lg, dec = lm_mod.decode_step(params, cfg, last[:, None], pos,
                                     caches, kernel_impl=kernel_impl)
        tokd = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        new_caches = _select_rows(isnew, cache_axes, fresh, dec)
        return new_caches, {"y": jnp.where(isnew, tok0, tokd)}

    decode = dynamic_actor("decode", "c", decode_control, ["x"], ["y"],
                           decode_fire, init=decode_init,
                           cost_flops=2 * cfg.d_model * cfg.d_model
                           * max(cfg.n_layers, 1) * B)

    # -- merge: fold tokens into the table, detect EOS/budget ------------
    def merge_control(tok):
        return {"table": jnp.int32(1), "y": _on_active(tok),
                "fb": jnp.int32(1)}

    def merge_fire(st, ins, rates):
        # A rate-0 idle step never reaches this body (table/fb would be
        # the only enabled ports, and y's window is all that changes the
        # table) — but the executor still runs it when any port is
        # enabled, so the y window must be masked by the active flags.
        del rates
        tbl = ins["table"][0]
        y = ins["y"][0]
        active = tbl[:, C_ACTIVE] > 0
        produced = tbl[:, C_PROD]
        gen_cols = jnp.arange(N, dtype=jnp.int32)[None, :]
        gen = tbl[:, HEADER + P:]
        gen = jnp.where(active[:, None] & (gen_cols == produced[:, None]),
                        y[:, None], gen)
        produced = produced + active.astype(jnp.int32)
        fin = active & ((y == eos) | (produced >= tbl[:, C_BUDGET]))
        header = jnp.stack([
            (active & ~fin).astype(jnp.int32),                    # ACTIVE
            tbl[:, C_REQ],
            tbl[:, C_POS] + active.astype(jnp.int32),             # POS
            produced,
            tbl[:, C_BUDGET],
            fin.astype(jnp.int32),                                # FIN
            jnp.where(active, y, tbl[:, C_LAST]),                 # LAST
            jnp.zeros((B,), jnp.int32),                           # NEW
            tbl[:, C_LAT],
            tbl[:, C_STATUS],                       # STATUS (OK on EOS fin)
            tbl[:, C_DEADLINE],
            tbl[:, C_AGE] + active.astype(jnp.int32),             # AGE
        ], axis=1)
        fb = jnp.concatenate([header, tbl[:, HEADER:HEADER + P], gen],
                             axis=1)
        return st, {"fb": fb}

    merge = dynamic_actor("merge", "c", merge_control, ["table", "y"],
                          ["fb"], merge_fire)

    # -- retire: dynamic sink collecting finished sequences --------------
    def retire_init():
        return {"gen": jnp.zeros((R, N), jnp.int32),
                "lens": jnp.zeros((R,), jnp.int32),
                "lat": jnp.zeros((R,), jnp.int32),
                "status": jnp.zeros((R,), jnp.int32),
                "done": jnp.zeros((R,), jnp.int32)}

    def retire_control(tok):
        return {"fin": _on_fin(tok)}

    def retire_fire(st, ins, rates):
        del rates
        rows = ins["fin"][0]
        m = rows[:, C_FIN] > 0
        req = jnp.where(m, rows[:, C_REQ], R)     # out of range -> dropped
        gen = rows[:, HEADER + P:]
        return {
            "gen": st["gen"].at[req].set(gen, mode="drop"),
            "lens": st["lens"].at[req].set(rows[:, C_PROD], mode="drop"),
            "lat": st["lat"].at[req].set(rows[:, C_LAT], mode="drop"),
            "status": st["status"].at[req].set(rows[:, C_STATUS],
                                               mode="drop"),
            "done": st["done"].at[req].set(1, mode="drop"),
        }, {}

    retire = dynamic_actor("retire", "c", retire_control, ["fin"], [],
                           retire_fire, init=retire_init,
                           finish=lambda st: st)

    # -- wiring ----------------------------------------------------------
    b = NetworkBuilder()
    for spec in (admission, gate, decode, merge, retire):
        b.actor(spec)
    tbl_shape, tok_i32 = (B, W), jnp.int32
    # The delay-token feedback FIFO carrying the per-slot decode state;
    # its initial token is the empty slot table.  delay (1) >= rate (1),
    # so the loop-carry channel may legally cross a partition boundary —
    # grid cores or mesh devices (ExecutionPlan(devices=k), see
    # repro.core.shard) — and the whole serving graph shards without a
    # device_assign constraint.
    # Slot-table channels declare SLOT_DOMAIN + the request-id column:
    # guarded runs flag a poisoned row with the DOMAIN fault bit the
    # moment admission writes it, and row_id_col lets fault / feed
    # reports name the offending request, not just the channel.
    slot_kw = dict(token_shape=tbl_shape, dtype=tok_i32,
                   domain=SLOT_DOMAIN, row_id_col=C_REQ)
    b.connect("merge.fb", "admission.fb", delay=1,
              initial_token=jnp.zeros(tbl_shape, jnp.int32), name="fb",
              **slot_kw)
    b.connect("admission.table", "merge.table", name="table", **slot_kw)
    b.connect("admission.x", "gate.x", name="x", **slot_kw)
    b.connect("admission.fin", "gate.fin", name="fin", **slot_kw)
    b.connect("gate.xa", "decode.x", name="xa", **slot_kw)
    b.connect("decode.y", "merge.y", token_shape=(B,), dtype=tok_i32,
              domain=SLOT_DOMAIN, name="y")
    b.connect("gate.fina", "retire.fin", name="fina", **slot_kw)
    for ctl_port, actor in (("c_gate", "gate"), ("c_dec", "decode"),
                            ("c_merge", "merge"), ("c_ret", "retire")):
        b.connect(f"admission.{ctl_port}", f"{actor}.c", token_shape=(3,),
                  dtype=tok_i32, name=f"ctl_{actor}")
    # Declared accept/EOS rate bounds (PRUNE-style): admission can admit
    # 0..B requests per firing, a slot's decode/retire ports are enabled
    # in 0..all firings — the matched-rates derivation tightens these to
    # "balanced" per channel, but the declaration documents the intended
    # envelope and keeps check_bounds decidable if a wiring change ever
    # drops a matched proof.
    for ep in ("gate.xa", "decode.x", "decode.y", "merge.y",
               "gate.fina", "retire.fin"):
        b.rate_bounds(ep, 0.0, 1.0)
    net = b.build(check_bounds=check_bounds)
    if return_bounds:
        return net, (b.bounds_report if check_bounds else b.check_bounds())
    return net


# --------------------------------------------------------------------- #
# Fault -> request mapping (the quarantine half of the resilience layer).
# --------------------------------------------------------------------- #
def faulted_requests(network: Network, err: Exception,
                     workload: ServingWorkload) -> List[int]:
    """Map a guarded serving fault back to the offending request ids.

    Only ``DOMAIN`` faults are mappable — they mean a slot-table row held
    values outside ``SLOT_DOMAIN``, which (for the fault classes the
    serving layer models, see ``faultinject.poison_request``) can only
    have entered through the staged workload.  Two mapping passes:

    * **primary** — scan the staged slabs themselves.  The guarded
      executor runs to quiescence before raising, so the poisoned row may
      have transited (and left) several rings; the workload is the one
      place the culprit is guaranteed to still be visible.
    * **secondary** — if partial state survived (``err.result.state``),
      scan the resident windows of each DOMAIN-faulting channel that
      declared a ``row_id_col``: out-of-domain rows vote with their
      request-id column.  Catches corruption injected *after* staging
      (e.g. ``faultinject.poison_tokens`` on a live ring).

    Returns sorted unique request ids; empty when the fault carries no
    DOMAIN bit (non-request faults — overflow, stall — are not a
    request's fault and must not quarantine anyone).
    """
    diag = getattr(err, "diagnostics", None)
    faults = diag.faults if diag is not None else ()
    dom = [f for f in faults if "DOMAIN" in f.faults]
    if not dom:
        return []
    lo, hi = SLOT_DOMAIN
    R = int(workload.prompts.shape[0])
    culprits: set = set()

    prompts = np.asarray(workload.prompts)
    bad_rows = np.any((prompts < lo) | (prompts > hi), axis=1)
    culprits.update(int(i) for i in np.nonzero(bad_rows)[0])
    for slab in (workload.budgets, workload.arrivals):
        vals = np.asarray(slab)
        bad = (vals < lo) | (vals > hi)
        culprits.update(int(i) for i in np.nonzero(bad)[0])

    state = getattr(getattr(err, "result", None), "state", None)
    if state is not None:
        for f in dom:
            spec = network.fifos.get(f.fifo)
            if spec is None or spec.row_id_col is None:
                continue
            buf = np.asarray(state.fifo(f.fifo).buf)
            if buf.ndim < 2:
                continue
            rows = buf.reshape(-1, buf.shape[-1])
            bad = np.any((rows < lo) | (rows > hi), axis=1)
            for r in np.nonzero(bad)[0]:
                rid = int(rows[r, spec.row_id_col])
                if 0 <= rid < R:
                    culprits.add(rid)
    return sorted(culprits)
