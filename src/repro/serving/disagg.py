"""Disaggregated prefill/decode serving over the ``pod`` mesh axis.

The colocated :class:`~repro.serving.engine.ServingEngine` interleaves at
most one prefill-chunk dispatch with one decode dispatch per tick, on one
K/V cache: chunked prefill exists to bound decode latency while long
prompts are admitted, and every prefill chunk a request needs is paid for
*between* the decode steps of everyone else's streams.  Disaggregation
splits the two phases onto disjoint submeshes of the ``pod`` axis —
prefill pods at device window ``[0, prefill_pods)``, decode pods after
them — so each phase runs under its own objective:

* **prefill pods** run chunked prefill into their own cache
  (``pcache``), with the chunk re-picked under the prefill objective
  (:data:`PREFILL_STEP_OVERHEAD`): with no decode stream to protect,
  per-dispatch overhead is the only thing the chunk trades against, so
  the planner leans large.
* **decode pods** run the round-robin fused decode step on the decode
  cache, under decode-role plans (shallow ``best_k`` when layers
  pipeline: the stage-ingress transfer serializes in front of the
  systolic schedule — ``sharding.pp_transfer_terms``).
* a finished prefill **hands off** the request's K/V pod->pod as a
  priced transfer: dense mode moves the slot's cache row, paged mode
  moves exactly the live pages named by the block table (only resident
  K/V crosses the ICI).  The transfer is a failure domain: the
  ``transfer.kv`` chaos point drops it, the engine retries up to
  ``max_retries``, and a persistent fault fails the sequence with a
  typed :class:`~repro.serving.errors.TransferFault` ->
  ``Outcome.FAILED`` — never a silent stall.
* ``pp_stages > 1`` additionally pipelines layers over the ``pod`` axis
  *within* each role (``parallel.pipeline.staged_step`` GPipe stages via
  ``collective_permute``), through ``lm.prefill_step_pp`` /
  ``lm.decode_step_pp``.  Each role's plans then price the stage
  boundary with the role's sign — prefill hides the send behind its
  deep schedule (boundary op, deeper ``best_k``), decode pays it as
  serialized ingress cycles (shallower ``best_k``) — so the same site
  legitimately collapses to different depths on the two submeshes.

**Equivalence contract.**  Greedy streams are bit-identical to the
colocated engine's: K/V writes are per-position projections (chunking
never changes them), the handoff copies bits, and the decode step runs
the same math — pipeline pricing moves plan *depth*, never values.  The
W8A8 exception applies unchanged (per-tile activation scales make tile
geometry part of the numerics), so a quantizing backend keeps the
colocated chunk instead of re-picking.

**Measurement model.**  One process simulates both roles, dispatching
them sequentially, but the engine keeps per-role busy clocks
(``stats["prefill_time_s"]`` / ``stats["decode_time_s"]``) — in a real
deployment the roles run concurrently on disjoint pods, so a request's
disaggregated TTFT excludes the *other* role's work.
``ttft_virtual[rid]`` records exactly that: prefill-pod busy time spent
on the request (admission -> handoff, transfer included) plus decode-pod
busy time to its first token.  The colocated comparator is the wall
TTFT, which pays every interleaved decode dispatch; the disagg makespan
is ``max`` of the role clocks where colocated pays their sum.  The
``disagg`` bench section reports both.

**Pod loss** (``disagg.pod`` chaos point): a decode pod dies mid-stream.
Dense mode preempts every DECODE-resident request (PR 8 recompute-on-
re-admission: ``resume_prompt`` = prompt + generated, re-queued at the
front, re-prefilled on the prefill pods, handed off again) and cold-
starts the decode cache; paged mode routes each decode-resident sequence
through the engine's standard ``_preempt``.  Recovered streams finish
``PREEMPTED_RETRIED`` and are bit-identical to undisturbed runs.

Out of scope: the radix prefix cache (``prefix_cache=True``) assumes one
cache owns the shared pages — cross-pod page ownership is rejected at
construction; paged mode with ``pp_stages > 1`` likewise (the paged
gather/scatter steps have no pipeline variant).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import planner
from repro.kernels import substrate
from repro.models import lm
from repro.parallel import sharding
from repro.serving.engine import (PREFILL_CHUNK_CHOICES, Request, ServeConfig,
                                  ServingEngine, Slot, jit_step)
from repro.serving.errors import Outcome, TransferFault
from repro.serving.paged import PagePool, PagedSeq

# Prefill-role chunk objective: with no decode stream to protect, the
# chunk only trades dispatch count against per-step cost, so the fixed
# per-dispatch overhead weighs heavier than in the colocated engine's
# default attention_plan call (which must also keep decode latency
# bounded between chunks).
PREFILL_STEP_OVERHEAD = 4.0


@dataclass(frozen=True)
class DisaggServeConfig(ServeConfig):
    """:class:`ServeConfig` plus the disaggregation geometry.

    ``prefill_pods`` / ``decode_pods`` size the two role submeshes;
    ``pp_stages`` pipelines layers over the ``pod`` axis within each role
    (``1`` = no pipeline; ``> 1`` requires ``prefill_pods == decode_pods
    == pp_stages`` and dense K/V)."""

    prefill_pods: int = 1
    decode_pods: int = 1
    pp_stages: int = 1


class DisaggServingEngine(ServingEngine):
    """Prefill/decode-disaggregated serving engine (see module docstring).

    Scheduling stays the base engine's (admission, deadlines, watchdog,
    snapshots, chaos scope); only the prefill path is re-routed onto the
    prefill role's cache + compiled steps, with the pod->pod K/V handoff
    bridging into the untouched decode path."""

    def __init__(self, cfg: ModelConfig, params,
                 serve_cfg: DisaggServeConfig, *, clock=time.perf_counter):
        if not isinstance(serve_cfg, DisaggServeConfig):
            raise TypeError("DisaggServingEngine needs a DisaggServeConfig "
                            f"(got {type(serve_cfg).__name__})")
        if serve_cfg.prefill_pods < 1 or serve_cfg.decode_pods < 1:
            raise ValueError(
                f"prefill_pods={serve_cfg.prefill_pods} / "
                f"decode_pods={serve_cfg.decode_pods}: each role needs at "
                f"least one pod")
        if serve_cfg.prefix_cache:
            raise ValueError(
                "prefix_cache=True is colocated-only: radix-shared pages "
                "assume one cache owns them, and the disaggregated handoff "
                "would either move shared pages twice or leave the decode "
                "pods reading pages they don't hold")
        pp = max(1, int(serve_cfg.pp_stages))
        if pp > 1:
            if serve_cfg.kv_pages:
                raise ValueError(
                    "pp_stages > 1 requires dense K/V (kv_pages=0): the "
                    "paged gather/scatter steps have no pipeline variant")
            if serve_cfg.prefill_pods != pp or serve_cfg.decode_pods != pp:
                raise ValueError(
                    f"pp_stages={pp} pipelines layers over each role's "
                    f"whole submesh: need prefill_pods == decode_pods == "
                    f"{pp}, got {serve_cfg.prefill_pods}+"
                    f"{serve_cfg.decode_pods}")

        super().__init__(cfg, params, serve_cfg, clock=clock)

        if pp > 1 and self.prefill_mode != "batched":
            raise ValueError("pp_stages > 1 requires the batched prefill "
                             "path (prefill_mode='batched' or 'auto' on a "
                             "supporting family)")

        # Role configs: same model, opposite plan objectives.  pp_role
        # engages sharding.use_pp_pricing inside the lm entry points (the
        # boundary site's plans re-pick under the role's transfer terms);
        # with pp_stages <= 1 the pricing scope is inert and role plans
        # are bit-for-bit the colocated ones.
        self.pp = pp
        if pp > 1:
            self.pcfg = dataclasses.replace(
                cfg, pp_role="prefill", pp_stages=pp, mesh_shape=(pp, 1, 1),
                pod_offset=0)
            self.dcfg = dataclasses.replace(
                cfg, pp_role="decode", pp_stages=pp, mesh_shape=(pp, 1, 1),
                pod_offset=serve_cfg.prefill_pods)
            # fail at construction, not mid-serve: the role windows need
            # prefill_pods + decode_pods devices, and the model must
            # support the pipeline (stage-divisible layers, batched
            # prefill)
            lm._check_pp(self.pcfg)
            lm._check_pp(self.dcfg)
            sharding.mesh_from_config(self.pcfg)
            sharding.mesh_from_config(self.dcfg)
            self._decode = jit_step(lm.decode_step_pp, self.dcfg)
            self._prefill = jit_step(lm.prefill_step_pp, self.pcfg)
        else:
            self.pcfg = dataclasses.replace(cfg, pp_role="prefill")
            self.dcfg = dataclasses.replace(cfg, pp_role="decode")
            self._decode = jit_step(lm.decode_step, self.dcfg)
            if self.prefill_mode == "batched":
                self._prefill = jit_step(lm.prefill_step, self.pcfg)
            else:
                # token-mode prefill runs the decode-path step, but on the
                # PREFILL pods (prefill cache, prefill-role plans)
                self._token_prefill_step = jit_step(lm.decode_step,
                                                    self.pcfg)
            if self.paged:
                self._decode_paged = jit_step(lm.decode_step_paged,
                                              self.dcfg)
                self._prefill_paged = jit_step(lm.prefill_step_paged,
                                               self.pcfg)

        # Prefill-role chunk re-pick (see PREFILL_STEP_OVERHEAD).  An
        # explicit serve_cfg.prefill_chunk still wins, and a W8A8 backend
        # keeps the colocated pick: its per-tile activation scales make
        # chunk geometry part of the numerics, and the equivalence
        # contract outranks the chunk objective there.
        if (self.prefill_mode == "batched" and not serve_cfg.prefill_chunk
                and not substrate.backend_act_quantizes(cfg.gemm_backend)):
            S = serve_cfg.max_seq
            self.prefill_chunk = min(S, max(1, planner.attention_plan(
                S, S, choices=PREFILL_CHUNK_CHOICES,
                step_overhead=PREFILL_STEP_OVERHEAD)))

        # The prefill pods' own K/V cache; self.cache stays the decode
        # pods'.  Paged mode mirrors the page payload arrays with a
        # shared PagePool/block-table numbering, so the handoff is a pure
        # payload copy at the live page indices.
        if self.paged:
            self.pcache = lm.init_paged_cache(
                cfg, serve_cfg.kv_pages, self.page_size)
        else:
            self.pcache = lm.init_cache(
                cfg, serve_cfg.max_batch, serve_cfg.max_seq)
        if pp > 1:
            # commit each cache to its role's device window up front (the
            # pipeline shard_map stages the n_super dim over 'pod'); the
            # handoff device_put below is then a real cross-window move
            self._pmesh = sharding.mesh_from_config(self.pcfg)
            self._dmesh = sharding.mesh_from_config(self.dcfg)
            self.pcache = jax.device_put(
                self.pcache, NamedSharding(self._pmesh, P("pod")))
            self.cache = jax.device_put(
                self.cache, NamedSharding(self._dmesh, P("pod")))

        self.stats.update(kv_transfer_pages=0, kv_transfer_bytes=0,
                          transfer_retries=0, pod_losses=0)
        # virtual role-clock marks per rid (see module docstring):
        # p0 = prefill busy at admission; pused = prefill busy spent on
        # the request (handoff inclusive); d0 = decode busy at handoff
        self._vt: Dict[int, dict] = {}
        self.ttft_virtual: Dict[int, float] = {}

    # ---------------------------------------------------------- handoff
    def _transfer_ok(self, detail: str) -> bool:
        """One ``transfer.kv`` chaos draw per attempt, retried up to the
        engine's retry budget.  True = the K/V move may proceed."""
        if self._chaos is None:
            return True
        retries = max(0, self.sc.max_retries)
        for attempt in range(retries + 1):
            if not self._chaos.fire("transfer.kv", detail):
                return True
            if attempt < retries:
                self.stats["transfer_retries"] += 1
        return False

    def _mark_handoff(self, req: Request):
        m = self._vt.get(req.rid)
        if m is not None:
            m["pused"] = self.stats["prefill_time_s"] - m["p0"]
            m["d0"] = self.stats["decode_time_s"]

    def _handoff_dense(self, slot: Slot) -> bool:
        """Move slot's prefilled cache row pod->pod.  The full row is
        copied: positions past ``prefill_len`` hold garbage, but decode
        writes each position before it is ever attended (the same
        write-before-read argument the fused decode step already relies
        on).  False = persistent transfer fault, request failed."""
        b = slot.index
        req = slot.req
        if not self._transfer_ok(f"rid={req.rid} slot={b}"):
            err = TransferFault(
                f"request {req.rid}: pod->pod K/V handoff dropped "
                f"{self.sc.max_retries + 1} times (retry budget spent)")
            self._finish(req, Outcome.FAILED,
                         f"{type(err).__name__}: {err}")
            slot.release()
            return False
        t0 = self.clock()
        row = jax.tree_util.tree_map(lambda p: p[:, b], self.pcache)
        if self.pp > 1:
            # the ICI hop: pull the row off the prefill window onto the
            # decode window before splicing it into the decode cache
            row = jax.device_put(row, NamedSharding(self._dmesh, P("pod")))
        self.cache = jax.tree_util.tree_map(
            lambda r, d: d.at[:, b].set(r), row, self.cache)
        jax.block_until_ready(self.cache)
        # transfer cost is prefill-pod egress: it gates the handoff, not
        # the decode pods' in-flight streams
        self.stats["prefill_time_s"] += self.clock() - t0
        self.stats["kv_transfer_bytes"] += int(sum(
            leaf[:, b].nbytes
            for leaf in jax.tree_util.tree_leaves(self.pcache)))
        self._mark_handoff(req)
        return True

    def _handoff_paged(self, seq: PagedSeq) -> bool:
        """Move exactly the live pages the block table names — positions
        ``[0, prefill_len)`` span the first ``ceil(prefill_len/page)``
        table entries — not the pool.  False = persistent fault."""
        req = seq.req
        n_pg = -(-seq.prefill_len // self.page_size) if seq.prefill_len \
            else 0
        idx = sorted({int(pg) for pg in seq.block_table[:n_pg]
                      if pg != PagePool.SCRATCH})
        if not idx:
            self._mark_handoff(req)
            return True
        if not self._transfer_ok(f"rid={req.rid} pages={len(idx)}"):
            err = TransferFault(
                f"request {req.rid}: pod->pod K/V handoff of {len(idx)} "
                f"pages dropped {self.sc.max_retries + 1} times "
                f"(retry budget spent)")
            self._finish(req, Outcome.FAILED,
                         f"{type(err).__name__}: {err}")
            self._release_paged(seq)
            return False
        ix = jnp.asarray(idx, jnp.int32)
        t0 = self.clock()
        self.cache = jax.tree_util.tree_map(
            lambda p, d: d.at[:, ix].set(p[:, ix]), self.pcache, self.cache)
        jax.block_until_ready(self.cache)
        self.stats["prefill_time_s"] += self.clock() - t0
        self.stats["kv_transfer_pages"] += len(idx)
        self.stats["kv_transfer_bytes"] += int(sum(
            leaf[:, ix].nbytes
            for leaf in jax.tree_util.tree_leaves(self.pcache)))
        self._mark_handoff(req)
        return True

    # ------------------------------------------------------------ prefill
    # The base engine's prefill tick, pointed at the prefill pods' cache;
    # a prefilled sequence is handed off instead of published.
    def _prefill_cache(self):
        return self.pcache

    def _set_prefill_cache(self, cache):
        self.pcache = cache

    def _prefilled(self, s):
        if self.paged:
            self._handoff_paged(s)
        else:
            self._handoff_dense(s)

    # ----------------------------------------------- virtual role clocks
    def _residents(self):
        if self.paged:
            return list(self.active)
        return [s for s in self.slots if s.req is not None]

    def _admit(self):
        before = {s.req.rid for s in self._residents()}
        super()._admit()
        pnow = self.stats["prefill_time_s"]
        decode_state = PagedSeq.DECODE if self.paged else Slot.DECODE
        for s in self._residents():
            if s.req.rid in before:
                continue
            self._vt[s.req.rid] = {"p0": pnow}
            if s.state == decode_state:
                # single-token prompt: nothing to prefill or hand off
                self._mark_handoff(s.req)

    def _decode_tick(self):
        decode_state = PagedSeq.DECODE if self.paged else Slot.DECODE
        pending = [s.req for s in self._residents()
                   if s.state == decode_state and not s.req.out_tokens]
        super()._decode_tick()
        dnow = self.stats["decode_time_s"]
        for req in pending:
            if req.out_tokens and req.rid not in self.ttft_virtual:
                m = self._vt.get(req.rid)
                if m is not None and "d0" in m:
                    self.ttft_virtual[req.rid] = \
                        m["pused"] + (dnow - m["d0"])

    # ----------------------------------------------------------- pod loss
    def _pod_loss(self):
        """A decode pod died: every decode-resident stream preempts and
        re-admits through the PR 8 recompute path (prefilled again on the
        prefill pods, handed off again); PREFILL residents live on the
        surviving role and continue untouched."""
        self.stats["pod_losses"] += 1
        if self.paged:
            for s in [q for q in self.active
                      if q.state == PagedSeq.DECODE]:
                self._preempt(s)
            return
        lost = False
        for slot in self.slots:
            if slot.state != Slot.DECODE:
                continue
            req = slot.req
            req.preemptions += 1
            self.stats["preemptions"] += 1
            req.resume_prompt = list(req.prompt) + list(req.out_tokens)
            slot.release()
            self._requeue(req)
            lost = True
        if lost:
            # the replacement decode pod starts cold; every re-admitted
            # stream rebuilds its row through prefill + handoff
            self.cache = jax.tree_util.tree_map(jnp.zeros_like, self.cache)

    def _step_inner(self):
        if self._chaos is not None and self._chaos.fire(
                "disagg.pod", f"tick={self._tick}"):
            self._pod_loss()
        return super()._step_inner()

    # ------------------------------------------------- snapshot / restore
    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["pcache"] = jax.tree_util.tree_map(np.asarray, self.pcache)
        return snap

    def _load_snapshot(self, snap: dict):
        super()._load_snapshot(snap)
        if "pcache" in snap:
            self.pcache = jax.tree_util.tree_map(jnp.asarray,
                                                 snap["pcache"])
