"""Continuous-batching serving engine on the dynamic-rate actor runtime.

Drop-in counterpart to :class:`repro.serve.Engine` that runs the
admission/decode/retire actor network of :mod:`repro.graphs.serving`
under any dynamic-capable :class:`ExecutionPlan` (host-dynamic by
default, megakernel via ``plan=ExecutionPlan(mode="megakernel")``, or
sharded across a device mesh via ``plan=ExecutionPlan(mode="dynamic",
devices=k)`` — the serving network's slot-table feedback channel
carries ``delay >= rate``, so it may legally cross devices and the
engine's greedy tokens stay identical at every device count; see
:mod:`repro.core.shard`).

Where the legacy engine groups requests into fixed batches and burns a
``decode_step`` on every slot until the *batch* finishes, the actor
engine admits requests into slots as they arrive and re-admits a slot
the moment its request retires (EOS or budget) — the dynamic-data-rate
win of the paper applied to serving.  Greedy tokens are identical
token-for-token to the legacy engine for dense model families (rows of
``prefill``/``decode_step`` are computed independently of their
batchmates at the same (B, P)/(B, 1) shapes).

``generate`` accepts an optional open-loop ``arrivals`` trace (one
arrival step per request, ascending — e.g. ``poisson_trace``); without
one every request is available at step 0 (the closed-loop batch case).

Resilience (the serving half of the PR 10 layer): per-request
``deadlines`` plus an engine-level ``queue_depth`` turn overload into
shed/timeout retirements instead of unbounded queueing, and
``generate(on_fault="quarantine")`` (guarded plans only) maps a
``NetworkFaultError`` back to the offending request ids via
``faulted_requests``, retires them with ``status="fault"``, and re-runs
the survivors from the pre-run checkpoint (the initial state — one
``generate`` is one run) with bounded retries.  Survivor tokens are
bit-identical to a fault-free run of the same survivor set: admission
timing cannot change a dense request's tokens (the module-level
bit-identity contract).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import ExecutionPlan
from repro.core.health import NetworkFaultError
from repro.core.trace import SPAN_BUILD
from repro.graphs.serving import (STATUS_FAULT, STATUS_OK, STATUS_SHED,
                                  STATUS_TIMEOUT, ServingWorkload,
                                  build_serving_network, faulted_requests,
                                  left_pad_prompts)
from repro.serve.engine import Request, Result, ServeConfig

PyTree = Any

_STATUS_STR = {STATUS_OK: "ok", STATUS_TIMEOUT: "timeout",
               STATUS_SHED: "shed", STATUS_FAULT: "fault"}


class ActorEngine:
    """Serving engine backed by the dynamic-rate actor network."""

    def __init__(self, cfg: ArchConfig, params: PyTree, scfg: ServeConfig,
                 plan: Optional[ExecutionPlan] = None,
                 queue_depth: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.queue_depth = queue_depth
        self.plan = plan if plan is not None else ExecutionPlan(
            mode="dynamic")
        if self.plan.mode not in ("dynamic", "megakernel"):
            raise ValueError(
                f"ActorEngine: plan mode {self.plan.mode!r} cannot run the "
                "serving feedback loop to data-dependent quiescence; use "
                "'dynamic' or 'megakernel'")
        #: Telemetry of the last generate() call.
        self.last_fire_counts: Optional[dict] = None
        self.last_sweeps: Optional[int] = None
        self.last_latency_steps: Optional[np.ndarray] = None
        self.last_program = None
        #: Per-request retirement status of the last generate() call
        #: ("ok" | "timeout" | "shed" | "fault"), aligned with the
        #: requests list.
        self.last_status: Optional[List[str]] = None
        #: Number of quarantine retries the last generate() call spent.
        self.last_retries: int = 0
        #: Decoded firing trace of the last generate() call (None unless
        #: the plan says trace=True).
        self.last_trace = None
        #: Sharding telemetry of the last generate() call (None unless
        #: the plan says devices > 1): bytes each sweep-barrier exchange
        #: moves across the mesh, from Program.stats().
        self.last_collective_bytes_per_sweep: Optional[int] = None

    # ------------------------------------------------------------------ #
    def _stage(self, requests: Sequence[Request],
               arrivals: Optional[np.ndarray],
               deadlines: Optional[np.ndarray]
               ) -> Tuple[ServingWorkload, Any]:
        scfg = self.scfg
        slab, lens = left_pad_prompts([r.prompt for r in requests],
                                      scfg.max_prompt)
        budgets = np.array([min(r.max_new, scfg.max_new) for r in requests],
                           np.int32)
        if arrivals is None:
            arrivals = np.zeros(len(requests), np.int32)
        arrivals = np.asarray(arrivals, np.int32)
        if arrivals.shape != (len(requests),):
            raise ValueError(
                f"ActorEngine: arrivals shape {arrivals.shape} != "
                f"({len(requests)},)")
        dl = None if deadlines is None else np.asarray(deadlines, np.int32)
        if dl is not None and dl.shape != (len(requests),):
            raise ValueError(
                f"ActorEngine: deadlines shape {dl.shape} != "
                f"({len(requests)},)")
        wl = ServingWorkload(prompts=slab, prompt_lens=lens,
                             budgets=budgets, arrivals=arrivals,
                             deadlines=dl)
        net = build_serving_network(
            self.cfg, self.params, wl, batch_size=scfg.batch_size,
            max_prompt=scfg.max_prompt, max_new=scfg.max_new,
            eos_id=scfg.eos_id, kernel_impl=scfg.kernel_impl,
            queue_depth=self.queue_depth)
        return wl, net

    def build_network(self, requests: Sequence[Request],
                      arrivals: Optional[np.ndarray] = None,
                      deadlines: Optional[np.ndarray] = None):
        """The serving network with these requests staged (exposed for
        tests/benchmarks that inspect the graph or pick their own plan)."""
        return self._stage(requests, arrivals, deadlines)[1]

    def generate(self, requests: List[Request],
                 arrivals: Optional[np.ndarray] = None,
                 deadlines: Optional[np.ndarray] = None,
                 on_fault: str = "raise",
                 max_retries: int = 2) -> List[Result]:
        if on_fault not in ("raise", "quarantine"):
            raise ValueError(
                f"ActorEngine: on_fault={on_fault!r}; pick 'raise' or "
                "'quarantine'")
        if on_fault == "quarantine" and not self.plan.guards:
            raise ValueError(
                "ActorEngine: on_fault='quarantine' needs a guarded plan "
                "(ExecutionPlan(guards=True)) — without fault flags there "
                "is no NetworkFaultError to map back to a request")
        live = [(i, r) for i, r in enumerate(requests) if r.max_new > 0]
        out: List[Optional[Result]] = [
            None if r.max_new > 0 else
            Result(tokens=np.zeros((0,), np.int32), prompt_len=len(r.prompt))
            for r in requests]
        self.last_retries = 0
        arr_all = (None if arrivals is None
                   else np.asarray(arrivals, np.int32))
        dl_all = (None if deadlines is None
                  else np.asarray(deadlines, np.int32))
        quarantined: List[int] = []      # original request indices
        if live:
            # Quarantine loop: every retry re-runs the survivor set from
            # the pre-run checkpoint (the initial network state) with the
            # culprits excluded; each round excludes >= 1 request, so the
            # loop is bounded by min(max_retries, len(live)).
            cur = list(live)
            while True:
                idxs = [i for i, _ in cur]
                arr = None if arr_all is None else arr_all[idxs]
                dl = None if dl_all is None else dl_all[idxs]
                with jax.profiler.TraceAnnotation(SPAN_BUILD):
                    wl, net = self._stage([r for _, r in cur], arr, dl)
                    prog = net.compile(self.plan)
                try:
                    res = prog.run()
                    break
                except NetworkFaultError as err:
                    if on_fault != "quarantine":
                        raise
                    culprits = faulted_requests(net, err, wl)
                    if (not culprits
                            or self.last_retries >= max_retries
                            or len(culprits) >= len(cur)):
                        raise
                    self.last_retries += 1
                    quarantined.extend(cur[j][0] for j in culprits)
                    cur = [cr for j, cr in enumerate(cur)
                           if j not in set(culprits)]
            self.last_program = prog
            self.last_fire_counts = (
                {k: int(v) for k, v in res.fire_counts.items()}
                if res.fire_counts is not None else None)
            self.last_sweeps = (int(res.sweeps)
                                if res.sweeps is not None else None)
            self.last_trace = res.trace
            self.last_collective_bytes_per_sweep = (
                prog.stats().collective_bytes_per_sweep
                if self.plan.devices > 1 else None)
            sink = prog.collect("retire", res.state)
            done = np.asarray(sink["done"])
            if not done.all():
                raise RuntimeError(
                    f"ActorEngine: {int((1 - done).sum())} request(s) never "
                    "retired (network quiesced early); check max_sweeps")
            gen = np.asarray(sink["gen"])
            lens = np.asarray(sink["lens"])
            status = np.asarray(sink["status"])
            self.last_latency_steps = np.asarray(sink["lat"])
            for j, (i, r) in enumerate(cur):
                st = _STATUS_STR.get(int(status[j]), "ok")
                # Timeouts keep the tokens they produced before the
                # deadline (partial result); sheds never ran.
                n = int(lens[j]) if st in ("ok", "timeout") else 0
                out[i] = Result(tokens=gen[j, :n].astype(np.int32),
                                prompt_len=len(r.prompt), status=st)
        for i in quarantined:
            out[i] = Result(tokens=np.zeros((0,), np.int32),
                            prompt_len=len(requests[i].prompt),
                            status="fault")
        self.last_status = [r.status for r in out]  # type: ignore[union-attr]
        return out  # type: ignore[return-value]
