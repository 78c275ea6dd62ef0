"""Continuous-batching serve engine: a shard-local slot scheduler over
mesh-sharded batched caches.

The lockstep ``ServeSession`` (one scalar ``pos`` for the whole batch)
wastes slots the moment sequences differ in length: everyone waits for the
longest prompt and the longest generation.  This engine admits and retires
sequences independently:

  * a request queue (FIFO by default; ``admission="srf"`` picks the
    shortest remaining request first, bounding TTFT when the queue exceeds
    prefill capacity) feeds ``n_slots`` cache slots;
  * one jitted decode executable advances ALL active slots per engine step
    with per-sequence positions ``pos [B]`` (free slots idle at pos = -1;
    their lanes compute masked garbage that is never read);
  * finished sequences free their slot immediately — the next queued
    request backfills it on the same engine step.

Per-request SWAN ``k`` (the paper's runtime-tunable compression) rides
along as a traced ``[B]`` operand: a batch can mix compression levels and
the decode step still compiles exactly once (see ``decode_cache_size`` —
asserted by tests/test_serve_engine.py).

Mesh sharding (``mesh=``, a Mesh with a ``data`` axis from
``repro.launch.mesh``): the ENTIRE batched serve state — dense/slab/ring
leaves, per-sequence ``pos``/``buf_pos``/``k`` operands, and the paged
pool (page axis sharded like the slab batch axis) — lives partitioned
over the mesh's data axis via per-leaf ``PartitionSpec``s from
``repro.sharding.serve_specs``.  Slots map to shards contiguously::

    slot  ->  (shard = slot // n_local,  lane = slot % n_local)

and the HOST scheduler is shard-local: admission places a request only in
a shard with a free lane (and, paged, free pages in that shard's block of
the pool — ``repro.runtime.page_pool`` keeps one free list per shard with
shard-local physical page indices and a per-shard trash page), the
budgeted round-robin prefill selection runs independently per shard, and
retirement returns pages to the owning shard's free list.  Every jitted
dispatch goes through ``sharding.api.shard_map_unchecked`` with
those specs, so each shard executes exactly the single-device engine's
computation on its local block — no cross-shard collectives anywhere on
the serve path — while the engine still issues exactly ONE prefill-chunk
dispatch and ONE decode dispatch per step regardless of shard count.
Model weights are replicated over the mesh by default
(``shard_params=True`` stores them sharded by ``repro.sharding.specs``
instead; they are gathered at dispatch).  The sharded engine is
token-identical to the single-device engine at any compression level
because lanes never interact (tests/test_sharded_engine.py;
benchmarks/bench_sharded_serve.py).  What remains for true multi-process
serving: per-host request routing in front of the shard-local scheduler
and a device-resident (rather than host-assembled) page table — the slot
-> (shard, lane) mapping and per-shard pools here are exactly the state a
per-process scheduler would own.

Prompt-length bucketing: prompts are padded to power-of-two buckets and the
true length rides along as a traced scalar, so prefill compiles
O(log max_seq) times instead of once per distinct prompt length.  Greedy
sampling happens on device (argmax inside the jitted decode step); the full
logits row-trip to host only when a request asks for temperature sampling.

Paged sparse cache (``paged=True``; SWAN only): instead of reserving
``[B, Kv, max_seq, k]`` sparse rows per slot, all slots share one page pool
``[n_pages, Kv, page_size, k]`` per layer side, addressed through a
host-managed page table (``repro.runtime.page_pool``).  Admission maps just
enough pages for the prompt's winnowed tokens, decode grows the mapping as
tokens land, and retirement returns pages for immediate reuse — cache
memory follows LIVE tokens, not ``n_slots * max_seq``.  Over-committed
pools hold admissions until pages free; with ``pool_grow=True`` the engine
instead GROWS the device pool (2x pages per shard, copy, extend the free
lists) up to the full-reservation cap, so admissions never wait and
mid-decode exhaustion disappears.  The paged engine is token-identical to
the slab engine (tests/test_paged_engine.py).

Chunked prefill (``prefill_chunk=C``, power of two; ``None`` = monolithic):
a monolithic admission stalls every active decode slot for the whole
prompt's prefill.  With chunking, each slot moves through a small state
machine::

    queued -> PREFILLING -> DECODING -> retired
               |  chunks of <= C tokens per engine step, via
               |  ``api.prefill_chunk`` straight into the slot's lanes of
               |  the BATCHED state (no single-slot transient at all: the
               |  slab path's init_serve_state(1, max_seq) admission
               |  allocation is gone, and paged admissions map pages per
               |  chunk, not per prompt)

Batched concurrent prefill (``prefill_slots=P``, ``prefill_budget=T``;
both PER SHARD under a mesh — each shard's lanes are its own device's
compute): up to ``P`` slots per shard may be PREFILLING at once, and every
engine step each shard round-robins its per-step token budget ``T``
(default ``P * C``) across them — a rotating pointer picks up to ``P``
in-flight prefills, each advances by one full chunk, and ALL shards'
selected chunks are packed into ONE jitted multi-slot executable
(``transformer.lm_prefill_chunk_batched``, traced ``[P]``
slot/start/true_len/k operands; under a mesh the lane axis is laid out
``[dp, P_local]`` so each shard's block only ever touches its own slots).
The per-shard lane count is bucketed to a power of two (dead lanes park
their slot index out of the SHARD'S range: slab/ring writes drop, paged
writes land on the shard's trash page), so an admission burst compiles
O(log n_slots × log chunk) executables instead of one per combination of
in-flight prefills — and each engine step issues exactly ONE chunk
dispatch plus ONE decode dispatch no matter how many prefills are in
flight or how many shards the mesh has.  Under a burst of admissions,
time-to-first-token is therefore O(prompt chunks), not O(queue depth ×
prompt chunks), and the round-robin keeps every in-flight prefill
advancing (no starvation) — benchmarks/bench_concurrent_prefill.py gates
the p99 TTFT win.

PREFILLING slots sit at ``pos = -1``; the decode step treats ``pos < 0``
lanes as dead (ring untouched, sparse/dense writes dropped or sent to the
trash page), which is what makes mid-prefill interleaving safe.  The last
chunk's logits seed the first sampled token and the slot flips to
DECODING.  Chunk boundaries are invisible in the cache, and per-lane chunk
boundaries never depend on the schedule — so chunked == monolithic,
batched-concurrent == serial, and sharded == single-device, token for
token, at any compression level (tests/test_chunked_prefill.py,
tests/test_concurrent_prefill.py, tests/test_sharded_engine.py).

Observability (``repro.obs``): the engine carries a ``metrics`` registry
(``metrics=False`` swaps in a no-op registry) and an optional ``trace``
JSONL event sink.  ALL instrumentation lives on the host side of the
dispatch boundaries — counters/gauges/histograms are plain Python updates
between jitted calls, trace events are step-indexed (and stamped with the
program clock), and nothing observable is threaded into a traced function
— so metrics on vs off produces identical tokens, identical dispatch
counts and identical compiled executables (pinned by
tests/test_obs_engine.py).  Every host phase of ``step()`` is an
``obs.span`` (``engine.step`` > ``engine.resolve`` > ``engine.sample``,
``engine.apply``, ``engine.admit``, ``engine.prefill`` >
``engine.dispatch.chunk``, ``engine.decode`` > ``engine.dispatch.decode``,
``engine.fetch``, ``engine.gauges``; see docs/observability.md): recorded,
and annotated in the profiler's trace, only while a ``jax.profiler``
session records (the ``profile_steps`` hook, or an outside one), a flag
check otherwise.

Byte accounting — ``cache_report()`` and the ``kv_cache_*`` gauges read
the SAME ``_cache_bytes()`` source (slab, paged and sharded paths share
it; per-shard entries always sum exactly to the totals).  The three
numbers mean:

  * ``reserved_bytes`` — bytes physically allocated on the device for
    cache state right now: the full slab/dense layout for slab engines
    (committed at init, so reserved == live there), or every pool page a
    paged engine has allocated (free pages included — the pool grows but
    never shrinks) plus the per-slot ring/dense buffers and the shipped
    table prefix.
  * ``live_bytes`` — bytes addressable by LIVE tokens right now: pages
    actually mapped to admitted sequences (paged), or the whole slab
    (slab engines address every row by construction).  This is the
    number that tracks generated tokens and drops on retirement.
  * ``page_table_shipped_bytes`` — bytes of the page-table PREFIX the
    next decode dispatch ships to the device ([n_slots, bucket] int32,
    bucketed over DECODING slots' mapped pages).  The host-resident full
    table is scheduler state, not device memory; only this prefix rides
    along on dispatches.

Executable warmup (``warmup()``; ``repro.runtime.warmup``): every shape
the scheduler can legally request is enumerable from static config —
decode page buckets, the chunk ``(prefix, P, C)`` matrix, prefill/insert
pads, the eager sampling/fetch one-offs.  ``warmup()`` dummy-dispatches
that whole family through the same jitted callables ``step()`` uses
(dead-lane operands, donation threaded through), so post-warmup traffic
triggers ZERO new XLA compiles (``executable_census()`` + the
process-global ``repro.obs.compile_events`` listener; machine-checked by
the swanlint Layer-2 ``warmup_checks`` and ``bench_warmup``).  With
``async_fetch=True`` the decode token transfer starts asynchronously at
dispatch and resolves at the top of the NEXT step, overlapping the copy
with host scheduling — token-, step- and dispatch-identical to the sync
path.  See docs/serving.md.
"""
from __future__ import annotations

import inspect
import math
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hybrid_cache as hc
from repro.core import paged_cache as pc
from repro.kernels.dispatch import (pallas_decode_supported,
                                    resolve_interpret, resolve_use_pallas)
from repro.kernels.swan_decode.swan_decode import paged_decode_grid
from repro.models import get_model, swan_applicable
from repro.obs import compile_events
from repro.obs.metrics import MetricsRegistry, NULL_REGISTRY
from repro.obs.trace import (EventTrace, StepProfiler, recording, span,
                             step_span)
from repro.runtime.page_pool import PagePool, PagePoolExhausted
from repro.runtime.sampling import sample_token
from repro.runtime.serve_loop import serve_cache_report
from repro.sharding.api import shard_map_unchecked
from repro.sharding.serve_specs import sanitize_tree, serve_state_pspecs
from repro.sharding.specs import dp_axes, params_pspecs

Params = Dict[str, Any]

# fixed histogram buckets, in ENGINE STEPS (deterministic scheduler time);
# powers of two to match the engine's bucketing story
TTFT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
GAP_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)
REQ_STEP_BUCKETS = (4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# host block on the decode token transfer, in ms (the engine.resolve span)
FETCH_MS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                    50.0, 100.0, 250.0, 1000.0, 4000.0)


@dataclass
class Request:
    """One generation request.

    ``k``: optional per-request SWAN retention override (<= swan.k_max) —
    the runtime compression knob, tunable per request without recompiling.
    ``arrival_step``: engine step at which the request becomes visible
    (deterministic trace replay; 0 = already waiting).
    """
    uid: Any
    tokens: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    eos: Optional[int] = None
    k: Optional[int] = None
    arrival_step: int = 0


@dataclass
class Completion:
    uid: Any
    tokens: List[int]
    prompt_len: int
    k: Optional[int]
    admitted_step: int
    finished_step: int
    # engine step that sampled the request's FIRST token (prefill
    # completion) — time-to-first-token in scheduler steps; what the
    # concurrent-prefill benchmark gates
    first_token_step: int = -1


class _PendingTokens:
    """An in-flight device->host token fetch.

    Created at the decode/chunk dispatch site: the tiny greedy ``[N]`` id
    vector and (only when temperature lanes exist) a power-of-two-bucketed
    gather of their logits rows start their host copies IMMEDIATELY via
    ``copy_to_host_async`` — so the transfer overlaps whatever host-side
    scheduling work runs next — and ``ServeEngine._resolve_tokens`` is the
    single designed point where the host finally blocks on the values.
    ``step`` pins the engine step that DISPATCHED the fetch, so deferred
    resolution (``async_fetch=True``) stamps completions, TTFT histograms
    and trace events with the same step the synchronous path would.
    """

    __slots__ = ("greedy", "rows", "temp", "picks", "step", "lanes")

    def __init__(self, greedy, rows, temp, picks, step, lanes):
        self.greedy = greedy      # device [N] int32 (argmax ids)
        self.rows = rows          # device [pow2(n_temp), V] or None
        self.temp = temp          # lane ids of temperature picks, in order
        self.picks = picks        # [(lane, Request, draw_index)]
        self.step = step          # engine step of the dispatch
        self.lanes = lanes        # slot ids (decode) — None for chunk


@dataclass
class _Slot:
    """Slot state machine: ``prefilling`` (chunked admission in flight;
    ``n_prefilled`` prompt tokens are in the cache, lane pos = -1 keeps the
    slot out of decode) -> ``decoding`` (normal per-step decode) ->
    retired (slot freed).  Monolithic admissions enter at ``decoding``."""
    req: Request
    generated: List[int] = field(default_factory=list)
    admitted_step: int = 0
    state: str = "decoding"
    n_prefilled: int = 0
    first_token_step: int = -1
    # engine step of the most recent sampled token — inter-token step-gap
    # accounting only (never consulted by the scheduler)
    last_token_step: int = -1


class ServeEngine:
    """Continuous-batching generation over a slot-based batched cache,
    optionally sharded over a device mesh's ``data`` axis."""

    def __init__(self, cfg, params, swan=None, projections=None,
                 max_seq: int = 4096, n_slots: int = 4, jit: bool = True,
                 paged: bool = False, page_size: int = 64,
                 n_pages: Optional[int] = None, bucket_prompts: bool = True,
                 prefill_chunk: Optional[int] = None,
                 prefill_slots: int = 1,
                 prefill_budget: Optional[int] = None,
                 mesh=None, shard_params: bool = False,
                 pool_grow: bool = False, admission: str = "fifo",
                 metrics=True, trace: Optional[EventTrace] = None,
                 use_pallas: Optional[bool] = None,
                 async_fetch: bool = False):
        self.cfg = cfg
        # passive process-global compile counting (repro.obs.compile_events)
        # — lets dispatch sites report mid-serve compiles into metrics and
        # lets warmup/audit gate "zero compiles after warmup()"
        compile_events.install()
        # observability sink: a shared registry may be passed in; False
        # swaps in the no-op registry (the call sites stay unconditional,
        # which is what lets tests prove on == off token-for-token)
        if isinstance(metrics, MetricsRegistry):
            self.metrics = metrics
        else:
            self.metrics = MetricsRegistry() if metrics else NULL_REGISTRY
        self.trace = trace
        self._profiler: Optional[StepProfiler] = None
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "encoder-decoder serving needs per-request encoder frames; "
                "use the lockstep ServeSession for whisper-style models")
        self.api = get_model(cfg)
        self.swan = swan if (swan and swan.enabled and swan_applicable(cfg)) else None
        self.projections = projections
        self.max_seq = max_seq
        self.n_slots = n_slots
        if self.swan is not None:
            self.swan.validate(cfg.d_head)
            if projections is None:
                raise ValueError("SWAN enabled but no projections given — "
                                 "run calibrate_swan first")
        if admission not in ("fifo", "srf"):
            raise ValueError(f"admission={admission!r}: 'fifo' or 'srf'")
        self.admission = admission
        self.pool_grow = pool_grow
        self._jit = jit

        # --- mesh topology: slot -> (shard, lane) ----------------------
        self.mesh = mesh
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError("serve mesh needs a 'data' axis — build it "
                                 "with repro.launch.mesh.make_serve_mesh")
            self._dpx = dp_axes(mesh)
            self.dp = int(np.prod([mesh.shape[a] for a in self._dpx]))
        else:
            self._dpx = None
            self.dp = 1
        if self.dp < 1 or n_slots % self.dp:
            raise ValueError(f"n_slots={n_slots} not divisible by the "
                             f"mesh's data-parallel degree {self.dp}")
        self.n_local = n_slots // self.dp
        if shard_params and (mesh is None or "model" not in mesh.axis_names):
            raise ValueError("shard_params needs a mesh with a 'model' axis")
        self.params = params

        prefill_sig = inspect.signature(self.api.prefill).parameters
        decode_sig = inspect.signature(self.api.decode_step).parameters
        # per-request k needs the family to thread k_active through
        # prefill/decode (transformer families: dense/moe/vlm; jamba/ssm
        # serve with their fixed config-level k)
        self._k_threading = (
            self.swan is not None
            and "k_active" in prefill_sig and "k_active" in decode_sig)
        # prompt bucketing needs true_len-aware prefill (transformer
        # families; recurrent state would absorb the padding junk)
        self._bucketing = bucket_prompts and "true_len" in prefill_sig
        # Pallas fast path: the kernel-backed decode/chunk read replaces
        # the pure-JAX gather when the family threads the flag AND the
        # cache shape is kernel-eligible (topk + ring buffer). use_pallas
        # None = auto: compiled kernels on TPU, off elsewhere (interpret
        # mode stays available for tests by forcing use_pallas=True on
        # CPU, where the kernels run under the Pallas interpreter).
        pallas_ok = ("use_pallas" in decode_sig
                     and pallas_decode_supported(self.swan))
        self.use_pallas = resolve_use_pallas(use_pallas) and pallas_ok
        if use_pallas and not pallas_ok:
            raise ValueError(
                "use_pallas=True but the model family or SWAN config has "
                "no kernel path (needs transformer-family decode with "
                "mode='topk' and buffer > 0)")
        self._pallas_interpret = resolve_interpret(None)
        # stat of the engine.dispatch.* spans: which implementation backed
        # the hot-path read
        self._kernel_stat = {"kernel": "pallas" if self.use_pallas
                             else "xla"}
        k_fill = 0 if self.swan is None else self.swan.k_max

        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if prefill_chunk < 1 or prefill_chunk & (prefill_chunk - 1):
                raise ValueError(f"prefill_chunk={prefill_chunk} must be a "
                                 "power of two")
            if max_seq % prefill_chunk:
                raise ValueError(f"max_seq={max_seq} not divisible by "
                                 f"prefill_chunk={prefill_chunk}")
            if self.api.prefill_chunk is None:
                raise ValueError(f"{cfg.family!r} family cannot resume a "
                                 "prefill mid-prompt (recurrent state) — "
                                 "chunked prefill unsupported")
        if prefill_slots < 1:
            raise ValueError(f"prefill_slots={prefill_slots} must be >= 1")
        if prefill_slots > 1 and prefill_chunk is None:
            raise ValueError("prefill_slots > 1 (batched concurrent "
                             "prefill) requires prefill_chunk")
        # per-shard: each shard's selected lanes form its own block of the
        # packed chunk dispatch
        self.prefill_slots = min(prefill_slots, self.n_local)
        # soft per-step token cap round-robined across in-flight prefills
        # (per shard): lanes are selected until the budget is spent, and
        # every selected lane still advances a FULL chunk — boundaries
        # never depend on the budget, which is what keeps the batched
        # scheduler token-identical to the serial one at any compression
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(f"prefill_budget={prefill_budget} must be >= 1")
        if prefill_budget is not None and prefill_chunk is None:
            raise ValueError("prefill_budget requires prefill_chunk — a "
                             "monolithic admission has no per-step budget")
        self.prefill_budget = (prefill_budget if prefill_budget is not None
                               else self.prefill_slots * (prefill_chunk or 0))

        self.paged = paged
        if paged:
            if self.swan is None:
                raise ValueError("paged=True requires SWAN: only the sparse "
                                 "sides have a paged layout")
            if (self.api.init_paged_state is None
                    or "page_tab" not in decode_sig):
                raise ValueError(f"{cfg.family!r} family has no paged cache")
            if max_seq % page_size:
                raise ValueError(f"max_seq={max_seq} not divisible by "
                                 f"page_size={page_size}")
            max_pages = max_seq // page_size
            # default pool: full per-shard reservation (+1 trash page per
            # shard) rounded up to a multiple of 8 pages per shard (extra
            # pages are plain free capacity) — operators shrink n_pages to
            # over-commit; live accounting still tracks tokens, and
            # admission waits for pages (or grows the pool, pool_grow)
            # instead of failing
            if n_pages is None:
                n_pages = self.dp * (
                    -(-(self.n_local * max_pages + 1) // 8) * 8)
            elif n_pages % self.dp:
                raise ValueError(f"n_pages={n_pages} not divisible by the "
                                 f"mesh's data-parallel degree {self.dp}")
            self.pool: Optional[PagePool] = PagePool(
                n_pages, max_pages, n_slots, page_size, n_shards=self.dp)
            self.pool.bind_obs(self.metrics, trace,
                               step_fn=lambda: self.step_count)
            self.state = self.api.init_paged_state(
                cfg, self.swan, n_slots, max_seq, n_pages, page_size)
        else:
            self.pool = None
            self.state = self.api.init_serve_state(cfg, self.swan, n_slots,
                                                   max_seq)
        sw, pj = self.swan, self.projections

        # --- mesh placement -------------------------------------------
        if mesh is not None:
            # data-parallel compute ONLY: the serve dispatch bodies are
            # lane-local (no split-S stat merge), so strip every non-dp
            # axis from the production serve-state specs — on a mesh that
            # also carries 'model', cache sequence dims must stay
            # replicated across it (sharding them without collectives in
            # the shard_map body would silently corrupt the softmax)
            keep = set(self._dpx)

            def _dp_only(spec):
                return P(*[ax if (ax in keep
                                  or (isinstance(ax, tuple)
                                      and set(ax) <= keep)) else None
                           for ax in tuple(spec)])

            self._state_specs = jax.tree_util.tree_map(
                _dp_only, serve_state_pspecs(self.state, mesh),
                is_leaf=lambda x: isinstance(x, P))
            self.state = jax.device_put(
                self.state, jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), self._state_specs))
            if shard_params:
                p_specs = sanitize_tree(params_pspecs(params, cfg, mesh),
                                        params, mesh)
                self.params = jax.device_put(
                    params, jax.tree_util.tree_map(
                        lambda s: NamedSharding(mesh, s), p_specs))
            else:
                self.params = jax.device_put(params, NamedSharding(mesh, P()))
        else:
            self._state_specs = None

        def prefill_fn(p, batch_in, state, k_act, true_len):
            kw = {}
            if self._k_threading:
                kw["k_active"] = k_act
            if self._bucketing:
                kw["true_len"] = true_len
            return self.api.prefill(p, cfg, batch_in, state, sw, pj, **kw)

        def decode_fn(p, token, pos, k_act, page_tab, state):
            kw = {}
            if self._k_threading:
                kw["k_active"] = k_act
            if self.paged:
                kw["page_tab"] = page_tab
            if self.use_pallas:
                kw["use_pallas"] = True
                kw["pallas_interpret"] = self._pallas_interpret
            with jax.named_scope("decode_step"):
                logits, state = self.api.decode_step(p, cfg, token, pos,
                                                     state, sw, pj, **kw)
            # device-side greedy sampling: ship back [B] token ids, not
            # [B, V] logits (host fetches logits only for temperature > 0)
            return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), state

        n_local = self.n_local

        def local_slot(slot):
            """Global slot index -> this shard's local lane (parked at the
            out-of-range value ``n_local`` off-shard, so scatters with
            mode="drop" write nothing there — NEVER left negative, which
            jnp index ops would wrap)."""
            if mesh is None:
                return slot
            ls = slot - _dp_index(mesh, self._dpx) * n_local
            return jnp.where((ls >= 0) & (ls < n_local), ls, n_local)

        def insert_fn(big, one, slot):
            ls = local_slot(slot)
            return jax.tree_util.tree_map(
                lambda b, o: b.at[:, ls].set(o[:, 0].astype(b.dtype),
                                             mode="drop"), big, one)

        def insert_paged_fn(big, one, slot, phys_rows):
            ls = local_slot(slot)
            if mesh is not None:
                phys_rows = jnp.where(ls < n_local, phys_rows, pc.TRASH_PAGE)
            return pc.paged_insert_prefill(big, one, ls, phys_rows,
                                           page_size)

        def make_chunk_fn(prefix_len):
            def chunk_fn(p, tokens, state, slot, start, k_act, true_len,
                         page_tab):
                kw = {}
                if self._k_threading:
                    kw["k_active"] = k_act
                if self.paged:
                    kw["page_tab"] = page_tab
                if self.use_pallas:
                    kw["use_pallas"] = True
                    kw["pallas_interpret"] = self._pallas_interpret
                with jax.named_scope("chunk_step"):
                    logits, state = self.api.prefill_chunk(
                        p, cfg, {"tokens": tokens}, state, slot, start, sw,
                        pj, true_len=true_len, prefix_len=prefix_len, **kw)
                # device-side greedy first-token sampling, mirroring
                # decode_fn: ship back [P] ids; logits rows cross to host
                # only for lanes that finished a temperature request's
                # prompt
                return (logits,
                        jnp.argmax(logits, axis=-1).astype(jnp.int32), state)
            return chunk_fn

        self._make_chunk_fn = make_chunk_fn
        # one jitted chunk executable family per STATIC slab/dense read
        # prefix bucket (None for paged — its read window is the shipped
        # page-table prefix); each family still retraces per (P, C, table
        # width) shape bucket exactly as static_argnums would
        self._chunk_fns: Dict[Optional[int], Any] = {}

        if mesh is not None:
            dpx = self._dpx
            rep, lane, lane2 = P(), P(dpx), P(dpx, None)
            st = self._state_specs
            tab = lane2 if paged else rep
            self._decode_specs = ((rep, lane, lane, lane, tab, st),
                                  (lane2, lane, st))
            self._chunk_specs = ((rep, lane2, st, lane, lane, lane, lane,
                                  tab), (lane2, lane, st))
            # monolithic admission: the batch=1 prefill is replicated
            # compute (every shard runs it; only the owner's insert lands)
            prefill_fn = shard_map_unchecked(prefill_fn, mesh,
                                             (rep, rep, rep, rep, rep),
                                             (rep, rep))
            decode_fn = shard_map_unchecked(decode_fn, mesh,
                                            *self._decode_specs)
            insert_fn = shard_map_unchecked(insert_fn, mesh,
                                            (st, rep, rep), st)
            insert_paged_fn = shard_map_unchecked(insert_paged_fn, mesh,
                                                  (st, rep, rep, rep), st)
        if jit:
            self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))
            self._decode = jax.jit(decode_fn, donate_argnums=(5,))
            self._insert = jax.jit(insert_fn, donate_argnums=(0,))
            self._insert_paged = jax.jit(insert_paged_fn, donate_argnums=(0,))
        else:
            self._prefill, self._decode = prefill_fn, decode_fn
            self._insert, self._insert_paged = insert_fn, insert_paged_fn

        # overlapped host/device step: defer the decode token fetch so all
        # host scheduling work of the NEXT step (admission, chunk packing,
        # table upload) runs while the copy is in flight — token-identical
        # to the synchronous path (tests/test_warmup.py)
        self.async_fetch = bool(async_fetch)
        self._pending: Optional[_PendingTokens] = None
        # jitted pool-grow executables keyed by the page delta, so repeated
        # grows of the same size reuse one compile (and land in the census)
        self._grow_fns: Dict[int, Any] = {}
        # set by warmup(): the executable family has been pre-compiled;
        # pool growth re-warms because it reshapes every state-carrying
        # executable's operands
        self._warmed = False
        self.warmup_report: Optional[Dict[str, Any]] = None

        self.queue: deque[Request] = deque()
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.slot_pos = np.full((n_slots,), -1, np.int32)   # next decode position
        self.slot_k = np.full((n_slots,), k_fill, np.int32)
        self._k_fill = k_fill
        self.next_tok = np.zeros((n_slots,), np.int32)
        self.step_count = 0
        self.completions: List[Completion] = []
        # per-shard round-robin pointers over prefill lanes
        self._prefill_rr = [s * self.n_local for s in range(self.dp)]
        # device copies of page-table prefixes, keyed by shipped width and
        # invalidated by the pool's dirty counter — decode steps and chunk
        # dispatches between page-mapping events reuse the last upload
        self._table_cache: Dict[int, Any] = {}
        # jitted-call counters per engine lifetime: the sharded-serve
        # benchmark gates that per-step dispatch count is independent of
        # shard count (one chunk + one decode dispatch per step)
        self.dispatches = {"prefill": 0, "chunk": 0, "decode": 0}

    def _obs_dispatch(self, kind: str, compiles: int = 0) -> None:
        """Record one hot-path dispatch: whether the Pallas kernels backed
        it, and any XLA compiles the dispatch triggered (``compiles`` is
        the ``compile_events.total()`` delta bracketing the call — zero in
        steady state, and zero from the very first request once
        :meth:`warmup` has run).  Its host time is the
        ``engine.dispatch.<kind>`` span."""
        if self.use_pallas:
            self.metrics.counter(
                "serve_pallas_dispatch_total",
                "hot-path dispatches backed by the Pallas kernels",
                kind=kind).inc()
        if compiles:
            self.metrics.counter(
                "serve_compile_total",
                "XLA backend compiles by phase (warmup vs mid-serve)",
                phase="serve", kind=kind).inc(compiles)

    # ------------------------------------------------------------------
    # Scheduling
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        with span("engine.submit", self.step_count, uid=req.uid):
            self._submit(req)

    def _submit(self, req: Request) -> None:
        if len(req.tokens) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.uid}: {len(req.tokens)}+{req.max_new_tokens} "
                f"tokens exceed max_seq={self.max_seq}")
        if req.k is not None:
            if self.swan is None:
                raise ValueError(f"request {req.uid}: per-request k needs SWAN")
            if not self._k_threading:
                raise ValueError(f"{self.cfg.family!r} family does not "
                                 "support per-request k overrides")
            if req.k > self.swan.k_max:
                raise ValueError(f"request {req.uid}: k={req.k} > allocated "
                                 f"k_max={self.swan.k_max}")
        self.queue.append(req)
        self.metrics.counter("serve_requests_submitted_total",
                             "requests accepted into the queue").inc()
        if self.trace is not None:
            self.trace.emit("submit", step=self.step_count, uid=req.uid,
                            prompt_len=len(req.tokens),
                            max_new_tokens=req.max_new_tokens, k=req.k,
                            arrival_step=req.arrival_step)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def pending(self) -> int:
        return sum(r.arrival_step <= self.step_count for r in self.queue)

    @property
    def done(self) -> bool:
        # an unresolved async token fetch means tokens (and possibly
        # retirements) are still owed — one more step() resolves it
        return (not self.queue and self.n_active == 0
                and self._pending is None)

    @staticmethod
    def _jit_cache_size(fn, what: str) -> int:
        """Compiled-executable count of one jitted callable.  Raises
        instead of guessing when the jit cache is not introspectable —
        a silent ``-1`` here once left the audit's executable-count
        bounds blind."""
        size = getattr(fn, "_cache_size", None)
        if not callable(size):
            raise RuntimeError(
                f"jit cache size not introspectable for the {what} "
                "dispatch on this JAX build — the executable census "
                "(and the swanlint count bounds) cannot run")
        return size()

    def executable_census(self) -> Dict[str, Any]:
        """Compiled-executable counts for EVERY jitted dispatch family the
        engine owns: decode, monolithic prefill, the chunk family (keyed
        by its static slab read-prefix bucket; ``"paged"`` for the
        table-prefix-bounded paged family), the two admission inserts and
        the pool-grow executables (keyed by page delta).  This is the one
        counting surface — the ``decode_cache_size``/``prefill_cache_size``
        properties, :meth:`warmup` and the swanlint Layer-2 audit all read
        it, so none of them can silently go blind.  Requires ``jit=True``
        (a no-jit engine has no compiled executables to count)."""
        if not self._jit:
            raise RuntimeError("executable_census requires jit=True")
        chunk = {("paged" if p is None else str(p)):
                 self._jit_cache_size(fn, f"chunk[prefix={p}]")
                 for p, fn in self._chunk_fns.items()}
        grow = {str(extra): self._jit_cache_size(fn, f"pool_grow[{extra}]")
                for extra, fn in self._grow_fns.items()}
        census: Dict[str, Any] = {
            "decode": self._jit_cache_size(self._decode, "decode"),
            "prefill": self._jit_cache_size(self._prefill, "prefill"),
            "chunk": chunk,
            "chunk_total": sum(chunk.values()),
            "insert": self._jit_cache_size(self._insert, "insert"),
            "insert_paged": self._jit_cache_size(self._insert_paged,
                                                 "insert_paged"),
            "pool_grow": grow,
            "pool_grow_total": sum(grow.values()),
        }
        census["total"] = (census["decode"] + census["prefill"]
                           + census["chunk_total"] + census["insert"]
                           + census["insert_paged"]
                           + census["pool_grow_total"])
        return census

    @property
    def decode_cache_size(self) -> int:
        """Compiled decode executables (1 == mixed-k batches share one);
        0 for a no-jit engine."""
        if not self._jit:
            return 0
        return self.executable_census()["decode"]

    @property
    def prefill_cache_size(self) -> int:
        """Compiled prefill executables, monolithic + chunked (bucketing
        keeps the total <= O(log max_seq)); 0 for a no-jit engine."""
        if not self._jit:
            return 0
        c = self.executable_census()
        return c["prefill"] + c["chunk_total"]

    def warmup(self, max_prompt_len: Optional[int] = None) -> Dict[str, Any]:
        """Pre-compile the engine's ENTIRE executable family before the
        first request: every (prompt-chunk x lane x slab-prefix /
        page-table-prefix) bucket the scheduler can legally dispatch, plus
        the host-side fetch/sampling shapes — so no request ever eats a
        mid-serve JIT compile.  Delegates to
        :func:`repro.runtime.warmup.warmup_engine` (dead-lane no-op
        dispatches through the SAME jitted callables ``step()`` uses,
        which is what actually populates the dispatch cache — an AOT
        ``lower().compile()`` would not).  Idempotent: a second call
        compiles nothing.  Returns the warmup report (also kept on
        ``self.warmup_report``); ``max_prompt_len`` trims the slab
        read-prefix family when the operator bounds admitted prompts."""
        from repro.runtime.warmup import warmup_engine
        report = warmup_engine(self, max_prompt_len=max_prompt_len)
        self._warmed = True
        self.warmup_report = report
        return report

    def shard_of(self, slot: int) -> int:
        """Which mesh shard owns ``slot`` (0 on a single device)."""
        return slot // self.n_local

    def _sample(self, logits, req: Request, n_prev: int) -> int:
        """Host-side sampling for temperature requests (greedy lanes use
        the device argmax) — shared f32-first helper, keyed per request by
        (seed, draw index)."""
        if req.temperature <= 0.0:
            return int(np.argmax(np.asarray(logits)))
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), n_prev)
        return int(sample_token(logits, req.temperature, key))

    def _start_fetch(self, logits, greedy, picks, step: int,
                     lanes=None) -> _PendingTokens:
        """Issue the device->host token transfer WITHOUT blocking: greedy
        lanes take the device argmax ([N] ints, tiny), and ONLY the
        temperature lanes' [V] rows are gathered on device — a greedy
        batch never round-trips the full logits.  The temperature index
        vector is padded to a power-of-two width (extra rows gather lane
        0 and are ignored at resolve time) so the eager gather compiles
        O(log n_slots) shapes, all of which :meth:`warmup` pre-compiles.
        Both transfers start via ``copy_to_host_async``; the host is free
        to do scheduling work until :meth:`_resolve_tokens`."""
        temp = [lane for lane, req, _ in picks if req.temperature > 0.0]
        rows = None
        if temp:
            idx = np.zeros((self._pow2(len(temp)),), np.int32)
            idx[:len(temp)] = temp
            rows = logits[jnp.asarray(idx)]
            rows.copy_to_host_async()
        greedy.copy_to_host_async()
        return _PendingTokens(greedy=greedy, rows=rows, temp=temp,
                              picks=list(picks), step=step, lanes=lanes)

    def _resolve_tokens(self, pending: _PendingTokens) -> List[int]:
        """Block on a :class:`_PendingTokens` transfer and sample one token
        per (lane, request, draw-index) triple.  This is the engine's ONLY
        decode-token host-sync point (allowlisted for swanlint SWAN102,
        like the ``_sample`` it calls) — everything upstream of it stays
        async.  The ``engine.sample`` span starts where the wait ends."""
        greedy = np.asarray(pending.greedy)
        rows = (np.asarray(pending.rows) if pending.rows is not None
                else None)
        temp = pending.temp
        with span("engine.sample", self.step_count):
            return [int(greedy[lane]) if req.temperature <= 0.0
                    else self._sample(rows[temp.index(lane)], req, draw)
                    for lane, req, draw in pending.picks]

    def _lane_tokens(self, logits, greedy, picks) -> List[int]:
        """Synchronous fetch: start the transfer and resolve it
        immediately (chunked-prefill first tokens, and the decode path
        when ``async_fetch`` is off)."""
        return self._resolve_tokens(
            self._start_fetch(logits, greedy, picks, self.step_count))

    def _resolve_pending(self) -> None:
        """Resolve the previous step's in-flight decode fetch, if any —
        called at the TOP of :meth:`step`, before admission, so the
        scheduler observes exactly the state the synchronous path would
        have left: tokens applied, retirements done and pages freed before
        any admission decision.  Metrics/trace rows are stamped with the
        DISPATCH step (``pending.step``), keeping TTFT / inter-token /
        completion accounting identical to ``async_fetch=False``."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        step_now = self.step_count
        with span("engine.resolve", step_now, timed=True) as sp:
            toks = self._resolve_tokens(pending)
        self._obs_fetch("async", sp.ms)
        self.step_count = pending.step      # stamp at the dispatch step
        try:
            with span("engine.apply", step_now):
                self._apply_decode_tokens(pending.lanes, toks)
        finally:
            self.step_count = step_now

    def _obs_fetch(self, mode: str, ms: float) -> None:
        self.metrics.histogram(
            "serve_token_fetch_ms", FETCH_MS_BUCKETS,
            "host block on the decode token transfer",
            mode=mode).observe(ms)

    def _apply_decode_tokens(self, lanes, toks) -> None:
        """Apply one decode step's sampled tokens to the scheduler state:
        advance positions, extend transcripts, feed ``next_tok``, stamp
        per-token metrics/trace, retire finished slots.  Shared verbatim
        by the sync path (same step) and the async path (resolved at the
        top of the next step, stamped with the dispatch step)."""
        gap_hist = self.metrics.histogram(
            "serve_inter_token_steps", GAP_BUCKETS,
            "engine steps between consecutive tokens of one request")
        tok_ctr = self.metrics.counter(
            "serve_tokens_generated_total",
            "sampled tokens (first tokens included)")
        for i, tok in zip(lanes, toks):
            s = self.slots[i]
            self.slot_pos[i] += 1
            s.generated.append(tok)
            self.next_tok[i] = tok
            gap_hist.observe(self.step_count - s.last_token_step)
            s.last_token_step = self.step_count
            tok_ctr.inc()
            if self.trace is not None:
                self.trace.emit("token", step=self.step_count,
                                uid=s.req.uid, slot=i,
                                index=len(s.generated) - 1, token=tok)
            self._maybe_retire(i)

    def _bucket_len(self, plen: int) -> int:
        """Smallest power-of-two bucket holding ``plen`` (capped at
        max_seq) — prefill compiles once per bucket, not per length."""
        if not self._bucketing:
            return plen
        return min(self._pow2(plen), self.max_seq)

    def _sparse_tokens(self, pos: int) -> int:
        """Winnowed (sparse-resident) tokens at decode position ``pos``."""
        return max(pos + 1 - self.swan.buffer, 0)

    def _page_bucket(self, slots) -> int:
        """Power-of-two bucket of logical pages covering every mapping in
        ``slots`` — the shipped page-table prefix width."""
        p_used = max([1] + [int(self.pool.n_mapped[i]) for i in slots])
        return min(self._pow2(p_used), self.pool.pages_per_seq)

    def _decode_bucket(self) -> int:
        dec = [i for i, s in enumerate(self.slots)
               if s is not None and s.state == "decoding"]
        return self._page_bucket(dec)

    def page_table_shipped_bytes(self) -> int:
        """Bytes of the page-table prefix a decode step ships to the device
        right now ([n_slots, p_bucket] int32) — the device-side table
        operand, as opposed to the host-resident full table.  The bucket
        covers DECODING slots, exactly as ``step()`` computes it
        (prefilling lanes are dead in the decode; chunk dispatches ship
        their own table prefix bucketed over the selected lanes)."""
        return self.n_slots * self._decode_bucket() * 4

    def _pow2(self, n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return b

    def _record_first_token(self, slot: int, tok: int) -> None:
        """Latency accounting for a slot's FIRST sampled token (prefill
        completion) — host-side only, after the slot already recorded
        ``first_token_step``.  TTFT is step-indexed against the request's
        arrival, matching what the concurrent-prefill benchmark gates."""
        s = self.slots[slot]
        ttft = self.step_count - s.req.arrival_step
        self.metrics.histogram(
            "serve_ttft_steps", TTFT_BUCKETS,
            "engine steps from request arrival to first token").observe(ttft)
        self.metrics.counter("serve_tokens_generated_total",
                             "sampled tokens (first tokens included)").inc()
        s.last_token_step = self.step_count
        if self.trace is not None:
            self.trace.emit("prefill_complete", step=self.step_count,
                            uid=s.req.uid, slot=slot,
                            prompt_len=len(s.req.tokens))
            self.trace.emit("first_token", step=self.step_count,
                            uid=s.req.uid, slot=slot, token=tok,
                            ttft_steps=ttft)
            self.trace.emit("token", step=self.step_count, uid=s.req.uid,
                            slot=slot, index=0, token=tok)

    def _admit(self, req: Request, slot: int) -> None:
        k_req = self.swan.k_max if (self.swan and req.k is None) else (req.k or 0)
        mode = "chunked" if self.prefill_chunk is not None else "monolithic"
        self.metrics.counter("serve_admissions_total",
                             "requests admitted into a slot",
                             mode=mode).inc()
        if self.trace is not None:
            self.trace.emit("admit", step=self.step_count, uid=req.uid,
                            slot=slot, shard=self.shard_of(slot),
                            prompt_len=len(req.tokens), k=req.k, mode=mode)
        if self.prefill_chunk is not None:
            # chunked admission: just claim the slot — chunks land as the
            # round-robin budget reaches this lane (see _advance_prefills),
            # straight into the slot's lanes of the batched state.  No
            # single-slot transient at all.
            if self.paged:
                # pages are MAPPED per chunk, but the prompt's whole winnow
                # need is HELD now — the admission gate checked it against
                # the shard's free pages, and without the hold a decoding
                # slot's growth could starve this in-flight prefill
                # mid-chunking
                self.pool.reserve(slot, self.pool.pages_for(
                    self._sparse_tokens(len(req.tokens) - 1)))
            self.slots[slot] = _Slot(req=req, admitted_step=self.step_count,
                                     state="prefilling")
            self.slot_pos[slot] = -1        # dead lane until prefill done
            self.slot_k[slot] = k_req
            return
        plen = len(req.tokens)
        pad_len = self._bucket_len(plen)
        if self.paged:
            # admission transients follow the PROMPT, not max_seq: the
            # single-slot prefill state is sized to the prompt bucket
            # (rounded to whole pages), and only that page prefix is
            # scattered into the pool
            ps = self.pool.page_size
            s1 = -(-pad_len // ps) * ps
        else:
            s1 = self.max_seq      # slab insert needs shape-matched slices
        state1 = self.api.init_serve_state(self.cfg, self.swan, 1, s1)
        toks = np.zeros((pad_len,), np.int32)
        toks[:plen] = np.asarray(req.tokens, np.int32)
        c0 = compile_events.total()
        with span("engine.dispatch.prefill", self.step_count, uid=req.uid,
                  stats=self._kernel_stat):
            logits, state1 = self._prefill(
                self.params, {"tokens": toks[None]}, state1,
                np.int32(k_req), np.int32(plen))
        dc = compile_events.total() - c0
        if dc:
            self.metrics.counter(
                "serve_compile_total",
                "XLA backend compiles by phase (warmup vs mid-serve)",
                phase="serve", kind="prefill").inc(dc)
        self.dispatches["prefill"] += 1
        self.metrics.counter("serve_dispatches_total",
                             "jitted dispatches by kind",
                             kind="prefill").inc()
        self.metrics.counter("serve_prefill_tokens_total",
                             "prompt tokens prefilled").inc(plen)
        if self.paged:
            self._ensure_pages(slot, self._sparse_tokens(plen - 1))
            self.state = self._insert_paged(
                self.state, state1, np.int32(slot),
                self.pool.table[slot, :s1 // ps])
        else:
            self.state = self._insert(self.state, state1, np.int32(slot))
        s = _Slot(req=req, admitted_step=self.step_count)
        first = self._sample(logits[0, -1], req, 0)
        s.generated.append(first)
        s.first_token_step = self.step_count
        self.slots[slot] = s
        self.slot_pos[slot] = plen
        self.slot_k[slot] = k_req
        self.next_tok[slot] = first
        self._record_first_token(slot, first)
        self._maybe_retire(slot)

    def _maybe_retire(self, slot: int) -> None:
        s = self.slots[slot]
        done = (len(s.generated) >= s.req.max_new_tokens
                or (s.req.eos is not None and s.generated[-1] == s.req.eos)
                or self.slot_pos[slot] >= self.max_seq)
        if not done:
            return
        reason = ("eos" if s.req.eos is not None
                  and s.generated[-1] == s.req.eos
                  else "max_tokens" if len(s.generated) >= s.req.max_new_tokens
                  else "max_seq")
        self.metrics.counter("serve_completions_total",
                             "retired requests by reason",
                             reason=reason).inc()
        self.metrics.histogram(
            "serve_request_steps", REQ_STEP_BUCKETS,
            "engine steps from admission to retirement").observe(
                self.step_count - s.admitted_step)
        if self.trace is not None:
            self.trace.emit("retire", step=self.step_count, uid=s.req.uid,
                            slot=slot, shard=self.shard_of(slot),
                            n_tokens=len(s.generated), reason=reason,
                            admitted_step=s.admitted_step,
                            first_token_step=s.first_token_step)
        self.completions.append(Completion(
            uid=s.req.uid, tokens=list(s.generated),
            prompt_len=len(s.req.tokens), k=s.req.k,
            admitted_step=s.admitted_step, finished_step=self.step_count,
            first_token_step=s.first_token_step))
        self.slots[slot] = None
        self.slot_pos[slot] = -1
        self.slot_k[slot] = self.swan.k_max if self.swan else 0
        self.next_tok[slot] = 0
        if self.paged:
            # pages return to the owning shard's free list NOW — a request
            # backfilled into this slot on the same engine step reuses them
            self.pool.free_slot(slot)

    def _next_request(self) -> Optional[Request]:
        """Admission policy over ARRIVED requests: FIFO takes the oldest;
        ``srf`` (shortest-remaining-first) takes the smallest total work
        (prompt + generation budget), FIFO-tiebroken, which bounds TTFT for
        short requests when the queue exceeds prefill capacity."""
        avail = [r for r in self.queue if r.arrival_step <= self.step_count]
        if not avail:
            return None
        if self.admission == "srf":
            return min(avail, key=lambda r: len(r.tokens) + r.max_new_tokens)
        return avail[0]

    def _admit_pending(self) -> None:
        while self.n_active < self.n_slots:
            nxt = self._next_request()
            if nxt is None:
                return
            free = [i for i, s in enumerate(self.slots) if s is None]
            if self.paged:
                # a request whose LIFETIME need exceeds a whole pool shard
                # can never run — grow the pool (pool_grow) or fail fast
                # instead of waiting forever
                lifetime = self.pool.pages_for(self._sparse_tokens(
                    len(nxt.tokens) + nxt.max_new_tokens - 1))
                if lifetime > self.pool.pages_per_shard - 1:
                    if not self.pool_grow:
                        raise PagePoolExhausted(
                            f"request {nxt.uid} needs {lifetime} pages over "
                            "its lifetime; each pool shard holds "
                            f"{self.pool.pages_per_shard - 1}")
                    self._grow_pool(lifetime + 1)
                # over-committed pool: admit only into a shard with enough
                # free pages for this prompt; otherwise grow (pool_grow) or
                # hold admissions until retirements free pages (FIFO
                # head-of-line on the policy's next pick)
                need = self.pool.pages_for(
                    self._sparse_tokens(len(nxt.tokens) - 1))
                fits = [i for i in free if need <=
                        self.pool.shard_free_pages(self.shard_of(i))]
                if not fits and self.pool_grow:
                    self._grow_pool(self.pool.pages_per_shard + max(need, 1))
                    fits = [i for i in free if need <=
                            self.pool.shard_free_pages(self.shard_of(i))]
                if not fits:
                    # held admission: counted per engine step spent waiting
                    self.metrics.counter(
                        "serve_admission_holds_total",
                        "steps an arrived request waited on pool pages").inc()
                    if self.trace is not None:
                        self.trace.emit("admission_hold",
                                        step=self.step_count, uid=nxt.uid,
                                        need_pages=need)
                    return
                slot = fits[0]
            else:
                slot = free[0]
            self.queue.remove(nxt)
            self._admit(nxt, slot)

    # ------------------------------------------------------------------
    # Paged-pool elasticity
    # ------------------------------------------------------------------

    def _ensure_pages(self, slot: int, n_tokens: int) -> None:
        """``pool.ensure`` with elasticity: when the pool is over-committed
        past live capacity, either grow it (``pool_grow``) or surface
        ``PagePoolExhausted`` to the caller."""
        if not self.pool_grow:
            self.pool.ensure(slot, n_tokens)
            return
        try:
            self.pool.ensure(slot, n_tokens)
        except PagePoolExhausted:
            self._grow_pool(self.pool.pages_per_shard
                            + self.pool.pages_for(n_tokens))
            self.pool.ensure(slot, n_tokens)

    def _grow_pool(self, min_pages_per_shard: int) -> None:
        """Grow the device pool: allocate at least ``min_pages_per_shard``
        (typically 2x) pages PER SHARD, copy the old pages over, extend the
        host free lists, and keep every page-table entry valid (local
        indices don't move; new pages append at the end of each shard's
        block).  Capped at the full-reservation size — at the cap a free
        slot can always admit and ``ensure`` can always succeed, so growth
        makes over-commit waits and mid-decode exhaustion impossible."""
        cap = self.n_local * self.pool.pages_per_seq + 1
        new_per = min(max(2 * self.pool.pages_per_shard,
                          min_pages_per_shard), cap)
        if new_per <= self.pool.pages_per_shard:
            raise PagePoolExhausted(
                f"page pool already at full reservation "
                f"({self.pool.pages_per_shard} pages/shard) — cannot grow")
        extra = new_per - self.pool.pages_per_shard

        # grow executables are cached per page DELTA (jit retraces per
        # input shape within one callable), so repeated growth by the
        # same stride recompiles nothing and the executable_census can
        # count the family
        fn = self._grow_fns.get(extra)
        if fn is None:
            def pad_pool(pool, _extra=extra):
                return jax.tree_util.tree_map(
                    lambda x: jnp.concatenate(
                        [x, jnp.zeros(x.shape[:1] + (_extra,) + x.shape[2:],
                                      x.dtype)], axis=1), pool)
            fn = pad_pool
            if self.mesh is not None:
                specs = self._state_specs["pool"]
                fn = shard_map_unchecked(fn, self.mesh, (specs,), specs)
            if self._jit:
                fn = jax.jit(fn, donate_argnums=(0,))
            self._grow_fns[extra] = fn
        state = dict(self.state)
        state["pool"] = fn(self.state["pool"])
        self.state = state
        old_per = self.pool.pages_per_shard
        self.pool.grow(new_per)
        self.metrics.counter("page_pool_grows_total",
                             "device pool growth events").inc()
        if self.trace is not None:
            self.trace.emit("pool_grow", step=self.step_count,
                            pages_per_shard_old=old_per,
                            pages_per_shard_new=new_per)
        if self._warmed:
            # the pool leaf changed shape, so every state-keyed executable
            # (decode, chunk family, grow) just went stale — re-warm now
            # and take the compiles as one visible warmup event instead of
            # scattered mid-serve cliffs on the next few dispatches
            self.warmup()

    # ------------------------------------------------------------------
    # Engine step
    # ------------------------------------------------------------------

    def _device_table(self, width: int):
        """Device copy of the page table's first ``width`` columns
        ([n_slots, width] int32, batch-sharded over the mesh's data axis —
        each shard sees its own slots' rows with shard-local physical
        indices) — cached per width and re-uploaded only when the host
        table changed (``pool.version`` dirty counter).  Decode steps and
        chunk dispatches between page-mapping events reuse the previous
        upload instead of shipping the table every step."""
        ver = self.pool.version
        hit = self._table_cache.get(width)
        if hit is None or hit[0] != ver:
            tab = np.ascontiguousarray(self.pool.table[:, :width])
            if self.mesh is not None:
                arr = jax.device_put(
                    tab, NamedSharding(self.mesh, P(self._dpx, None)))
            else:
                arr = jnp.asarray(tab)
            hit = (ver, arr)
            self._table_cache[width] = hit
        return hit[1]

    def _select_prefills(self, shard: int):
        """Round-robin up to ``prefill_slots`` PREFILLING lanes of
        ``shard`` within its per-step token budget — a SHARD-LOCAL
        decision: each shard has its own rotating pointer, so every
        in-flight prefill keeps advancing (no starvation when more
        prefills are in flight than ``prefill_slots``); each selected lane
        advances one FULL chunk, so per-lane chunk boundaries — and
        therefore tokens — never depend on the schedule."""
        lo = shard * self.n_local
        cands = [i for i in range(lo, lo + self.n_local)
                 if self.slots[i] is not None
                 and self.slots[i].state == "prefilling"]
        if not cands:
            return []
        rr = self._prefill_rr[shard]
        order = sorted(cands, key=lambda j: (j - rr) % self.n_slots)
        sel: List[int] = []
        spent = 0
        for i in order:
            if len(sel) >= self.prefill_slots or spent >= self.prefill_budget:
                break
            s = self.slots[i]
            sel.append(i)
            spent += min(len(s.req.tokens) - s.n_prefilled, self.prefill_chunk)
        self._prefill_rr[shard] = (sel[-1] + 1) % self.n_slots
        return sel

    def _advance_prefills(self) -> None:
        """Advance every shard's round-robin-selected in-flight prefills by
        one chunk EACH, packed into ONE batched chunk dispatch.  The lane
        axis is laid out ``[dp, P_local]`` — shard ``s``'s block holds only
        its own slots (as LOCAL lane indices), which is what lets the
        dispatch shard_map over the data axis with no cross-shard traffic.
        The per-shard lane count is bucketed to a power of two (dead lanes
        park slot = n_local, out of the shard's range) and full chunks
        share one width, so admission bursts compile O(log n_slots × log
        chunk) executables (times a slab-prefix or paged-table bucket
        dimension)."""
        sels = [self._select_prefills(s) for s in range(self.dp)]
        widest = max(len(s) for s in sels)
        if widest == 0:
            return
        Pl = self._pow2(widest)
        n_lanes = self.dp * Pl
        lens: Dict[int, int] = {}
        pads = []
        for sel in sels:
            for i in sel:
                s = self.slots[i]
                rem = len(s.req.tokens) - s.n_prefilled
                t = min(rem, self.prefill_chunk)
                lens[i] = t
                pads.append(self.prefill_chunk if rem >= self.prefill_chunk
                            else self._pow2(t))
        C = max(pads)
        toks = np.zeros((n_lanes, C), np.int32)
        slot_v = np.full((n_lanes,), self.n_local, np.int32)  # dead: local OOB
        start_v = np.zeros((n_lanes,), np.int32)
        tlen_v = np.ones((n_lanes,), np.int32)
        k_v = np.full((n_lanes,), self._k_fill, np.int32)
        picks = []                                  # (lane, global slot)
        for sh, sel in enumerate(sels):
            for j, i in enumerate(sel):
                lane = sh * Pl + j
                s = self.slots[i]
                st0, t = s.n_prefilled, lens[i]
                toks[lane, :t] = np.asarray(s.req.tokens[st0:st0 + t],
                                            np.int32)
                slot_v[lane] = i - sh * self.n_local
                start_v[lane] = st0
                tlen_v[lane] = t
                k_v[lane] = self.slot_k[i]
                picks.append((lane, i))
        sel_all = [i for _, i in picks]
        if self.paged:
            for lane, i in picks:
                # map pages for the tokens this chunk winnows; overshoot
                # writes past them land on the trash page and are rewritten
                # by the next chunk once its pages exist
                self._ensure_pages(i, self._sparse_tokens(
                    int(start_v[lane]) + lens[i] - 1))
            pg = self._pow2(max(1, max(int(self.pool.n_mapped[i])
                                       for i in sel_all)))
            page_tab = self._device_table(min(pg, self.pool.pages_per_seq))
            prefix = None               # the page_tab prefix bounds reads
        else:
            page_tab = np.zeros((), np.int32)           # unused operand
            prefix = min(self._pow2(int(start_v.max()) + C), self.max_seq)
        c0 = compile_events.total()
        with span("engine.dispatch.chunk", self.step_count,
                  stats=self._kernel_stat):
            logits, greedy, self.state = self._chunk_call(
                self.params, toks, self.state, slot_v, start_v, k_v, tlen_v,
                page_tab, prefix=prefix)
        self._obs_dispatch("chunk", compiles=compile_events.total() - c0)
        self.dispatches["chunk"] += 1
        self.metrics.counter("serve_dispatches_total",
                             "jitted dispatches by kind", kind="chunk").inc()
        self.metrics.counter("serve_prefill_tokens_total",
                             "prompt tokens prefilled").inc(
                                 sum(lens[i] for _, i in picks))
        if self.trace is not None:
            self.trace.emit("chunk_dispatch", step=self.step_count,
                            lanes=len(picks), slots=sel_all,
                            tokens=sum(lens[i] for _, i in picks))
        fins = []
        for lane, i in picks:
            s = self.slots[i]
            s.n_prefilled += lens[i]
            if s.n_prefilled == len(s.req.tokens):      # prompt complete
                fins.append((lane, i))
        if not fins:
            return
        with span("engine.prefill.tokens", self.step_count):
            firsts = self._lane_tokens(
                logits, greedy,
                [(lane, self.slots[i].req, 0) for lane, i in fins])
        for (lane, i), first in zip(fins, firsts):
            s = self.slots[i]
            s.state = "decoding"
            s.generated.append(first)
            s.first_token_step = self.step_count
            self.slot_pos[i] = len(s.req.tokens)
            self.next_tok[i] = first
            self._record_first_token(i, first)
            self._maybe_retire(i)

    def _chunk_jit(self, prefix: Optional[int]):
        """The (possibly jitted) batched chunk executable family for a
        STATIC slab/dense read-prefix bucket (one jit per bucket — the
        moral equivalent of static_argnums, kept explicit so the mesh path
        can close the prefix into its shard_map body).  Exposed separately
        from ``_chunk_call`` so ``lower_chunk`` can AOT-lower the same
        cached callable the scheduler dispatches through."""
        fn = self._chunk_fns.get(prefix)
        if fn is None:
            fn = self._make_chunk_fn(prefix)
            if self.mesh is not None:
                fn = shard_map_unchecked(fn, self.mesh, *self._chunk_specs)
            if self._jit:
                fn = jax.jit(fn, donate_argnums=(2,))
            self._chunk_fns[prefix] = fn
        return fn

    def _chunk_call(self, *args, prefix: Optional[int]):
        return self._chunk_jit(prefix)(*args)

    # ------------------------------------------------------------------
    # AOT lowering (compiled-dispatch audit + warmup)
    # ------------------------------------------------------------------

    def lower_decode(self, page_bucket: Optional[int] = None):
        """AOT-lower the decode dispatch for the shapes ``step()`` would
        use right now, WITHOUT executing it: returns ``jax.stages.Lowered``
        whose ``.compile().as_text()`` is the post-optimization HLO the
        swanlint auditor scans for host transfers and stray collectives.
        ``page_bucket`` overrides the shipped page-table width (paged
        engines; ignored for slab).  Lowers the SAME jitted callable the
        scheduler dispatches through, so the audited artifact is the
        production executable, not a re-derivation."""
        if not self._jit:
            raise RuntimeError("lower_decode requires jit=True")
        i32v = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
        if self.paged:
            width = page_bucket if page_bucket is not None \
                else self._decode_bucket()
            tab = jax.ShapeDtypeStruct((self.n_slots, width), jnp.int32)
        else:
            tab = jax.ShapeDtypeStruct((), jnp.int32)
        return self._decode.lower(self.params, i32v, i32v, i32v, tab,
                                  self.state)

    def lower_chunk(self, n_lanes: Optional[int] = None,
                    chunk: Optional[int] = None,
                    page_bucket: Optional[int] = None,
                    prefix: Optional[int] = None):
        """AOT-lower one chunked-prefill dispatch shape (defaults: one
        lane per shard, a full ``prefill_chunk`` of tokens, the smallest
        covering slab prefix / page bucket) — same contract as
        ``lower_decode``."""
        if not self._jit:
            raise RuntimeError("lower_chunk requires jit=True")
        C = chunk if chunk is not None else (self.prefill_chunk or 8)
        lanes = n_lanes if n_lanes is not None else self.dp
        i32v = jax.ShapeDtypeStruct((lanes,), jnp.int32)
        toks = jax.ShapeDtypeStruct((lanes, C), jnp.int32)
        if self.paged:
            width = page_bucket if page_bucket is not None else 1
            tab = jax.ShapeDtypeStruct((self.n_slots, width), jnp.int32)
            prefix = None               # the page_tab prefix bounds reads
        else:
            tab = jax.ShapeDtypeStruct((), jnp.int32)
            if prefix is None:
                prefix = self._bucket_len(C)
        fn = self._chunk_jit(prefix)
        return fn.lower(self.params, toks, self.state, i32v, i32v, i32v,
                        i32v, tab)

    def step(self) -> int:
        """One scheduler iteration: resolve the previous step's in-flight
        token fetch (async mode) → admit → one batched multi-slot prefill
        chunk dispatch → one batched decode dispatch → retire (or stash
        the fetch for the next step when ``async_fetch``).  Returns the
        number of sequences that finished during this call — with
        ``async_fetch`` a dispatch's completions surface one ``step()``
        call later (the tokens are identical; only the host-visible
        boundary shifts)."""
        if self._profiler is not None:
            self._profiler.step_start(self.step_count)
        n_done0 = len(self.completions)
        n = self.step_count
        with step_span("engine.step", n):
            # the previous step's decode tokens land BEFORE any scheduling
            # decision, so admission/chunking see the same world as sync
            # mode
            self._resolve_pending()
            with span("engine.admit", n):
                self._admit_pending()
            if self.prefill_chunk is not None:
                with span("engine.prefill", n):
                    self._advance_prefills()
            active = [i for i, s in enumerate(self.slots)
                      if s is not None and s.state == "decoding"]
            if active:
                self._decode_active(active)
            self.step_count += 1
            with span("engine.gauges", n):
                self._sample_gauges()
        if self._profiler is not None:
            self._profiler.step_end(self.step_count)
        return len(self.completions) - n_done0

    def _decode_active(self, active: List[int]) -> None:
        """One batched decode dispatch over the DECODING lanes, then either
        start the token fetch (``async_fetch``) or resolve and apply it."""
        n = self.step_count
        with span("engine.decode", n):
            stats = self._kernel_stat
            if self.paged:
                # grow each sequence's page mapping to cover the token its
                # decode step is about to winnow (grows the pool, or raises
                # PagePoolExhausted, if over-committed past live capacity)
                for i in active:
                    self._ensure_pages(i, self._sparse_tokens(
                        int(self.slot_pos[i])))
                # ship only a power-of-two bucket of logical pages: the
                # attention gather then materialises a view sized by LIVE
                # pages, not max_seq (transient memory follows tokens
                # too); one decode executable per bucket — O(log
                # max_pages) total.  The upload itself is cached
                # (dirty-flag) in _device_table.
                width = self._page_bucket(active)
                page_tab = self._device_table(width)
                if recording():
                    stats = self._grid_stats(active, width)
            else:
                page_tab = np.zeros((), np.int32)       # unused operand
            c0 = compile_events.total()
            with span("engine.dispatch.decode", n, stats=stats):
                logits, greedy, self.state = self._decode(
                    self.params, self.next_tok, self.slot_pos, self.slot_k,
                    page_tab, self.state)
            self._obs_dispatch("decode", compiles=compile_events.total() - c0)
            self.dispatches["decode"] += 1
            self.metrics.counter("serve_dispatches_total",
                                 "jitted dispatches by kind",
                                 kind="decode").inc()
            if self.trace is not None:
                self.trace.emit("decode_dispatch", step=n, lanes=len(active))
            picks = [(i, self.slots[i].req, len(self.slots[i].generated))
                     for i in active]
        if self.async_fetch:
            # start the device->host copy now, consume it at the top of
            # the NEXT step — the host does a full step of scheduling work
            # while the transfer is in flight
            with span("engine.fetch", n):
                self._pending = self._start_fetch(
                    logits, greedy, picks, n, lanes=active)
            return
        with span("engine.resolve", n, timed=True) as sp:
            toks = self._lane_tokens(logits, greedy, picks)
        self._obs_fetch("sync", sp.ms)
        with span("engine.apply", n):
            self._apply_decode_tokens(active, toks)

    def _grid_stats(self, active: List[int], width: int) -> Dict[str, Any]:
        """Occupancy of the paged decode kernel's grid, for the
        ``engine.dispatch.decode`` span: ``grid_pages`` (slot, page) cells
        it iterates on every shard (read from the kernel's own grid for
        one KV head over the shipped page bucket) and ``live_pages`` of
        them that hold a decoding lane's winnowed tokens."""
        ps = self.pool.page_size
        live = sum(-(-self._sparse_tokens(int(self.slot_pos[i])) // ps)
                   for i in active)
        grid = self.dp * math.prod(paged_decode_grid(self.n_local, 1, width))
        return {**self._kernel_stat, "grid_pages": grid, "live_pages": live}

    def profile_steps(self, n_steps: int, logdir: str) -> None:
        """Capture one ``jax.profiler`` trace spanning the next
        ``n_steps`` engine steps (admission, chunk dispatch and decode
        dispatch included) into ``logdir``."""
        self._profiler = StepProfiler(logdir, n_steps, trace=self.trace)

    def run(self, requests: Optional[Sequence[Request]] = None,
            max_steps: Optional[int] = None) -> List[Completion]:
        """Submit ``requests`` and step until everything drains (or
        ``max_steps``).  Returns completions in finish order."""
        for r in requests or ():
            self.submit(r)
        n0 = len(self.completions)
        steps = 0
        while not self.done and (max_steps is None or steps < max_steps):
            self.step()
            steps += 1
        return self.completions[n0:]

    # ------------------------------------------------------------------
    # Accounting
    # ------------------------------------------------------------------

    def _n_attn(self) -> int:
        return sum(1 for i in range(self.cfg.n_layers)
                   if self.cfg.layer_kind(i) == "attn")

    def _cache_bytes(self) -> Dict[str, Any]:
        """Single source of truth for cache byte accounting.  Both
        :meth:`cache_report` and the per-step ``kv_cache_*`` /
        ``shard_kv_cache_*`` gauges read THIS, so the two surfaces can
        never drift apart (asserted in tests/test_obs_engine.py).

        Returns ``reserved_bytes`` / ``live_bytes`` totals plus a
        ``shards`` breakdown whose entries sum exactly to them
        (``shards`` is ``None`` for recurrent-state families, which have
        no row-granular layout to split); paged mode adds
        ``page_table_shipped_bytes``, the shipped table-prefix operand.
        """
        if self.api.init_paged_state is None:
            # recurrent-state families: analytic Eq. 1 bytes only
            b = serve_cache_report(self.cfg, self.swan, self.n_slots,
                                   self.max_seq)["bytes"]
            return {"reserved_bytes": b, "live_bytes": b, "shards": None}
        n_attn = self._n_attn()
        if not self.paged:
            # live = bytes resident in the state arrays; reserved = the
            # analytic worst-case layout.  The slab engine commits the
            # worst case at init, so the two must coincide — a real
            # invariant that catches layout/accounting drift.
            live = sum(x.nbytes for x in
                       jax.tree_util.tree_leaves(self.state))
            if self.swan is None:
                reserved = n_attn * hc.dense_cache_bytes(
                    self.cfg, self.n_slots, self.max_seq)
                shard_res = n_attn * hc.dense_cache_bytes(
                    self.cfg, self.n_local, self.max_seq)
            else:
                reserved = n_attn * (
                    hc.cache_bytes(self.cfg, self.swan, self.n_slots,
                                   self.max_seq)
                    + self.n_slots * self.swan.buffer * 4)      # buf_pos
                shard_res = n_attn * (
                    hc.cache_bytes(self.cfg, self.swan, self.n_local,
                                   self.max_seq)
                    + self.n_local * self.swan.buffer * 4)
            assert reserved == live, \
                f"slab reserved {reserved} != resident {live}"
            # the slab layout is linear in the batch axis, so each shard
            # carries exactly its slots' share
            return {"reserved_bytes": reserved, "live_bytes": reserved,
                    "shards": [{"reserved_bytes": shard_res,
                                "live_bytes": shard_res}
                               for _ in range(self.dp)]}
        page_b = pc.page_bytes(self.cfg, self.swan, self.pool.page_size)
        # device overhead counts the SHIPPED page-table prefix (the actual
        # per-step device operand), not the host-resident numpy table
        bucket = self._decode_bucket()
        overhead = (pc.ring_bytes(self.cfg, self.swan, self.n_slots)
                    + self.n_slots * bucket * 4)
        # per-shard: each shard owns its block of the pool, its slots'
        # rings, and its rows of the shipped table prefix (ring_bytes and
        # the table are linear in the batch axis, page blocks are equal by
        # construction — so the entries sum exactly to the totals)
        sh_over = (pc.ring_bytes(self.cfg, self.swan, self.n_local)
                   + self.n_local * bucket * 4)
        return {
            "reserved_bytes": self.pool.reserved_bytes(page_b) + overhead,
            "live_bytes": self.pool.live_bytes(page_b) + overhead,
            "page_table_shipped_bytes": self.n_slots * bucket * 4,
            "shards": [
                {"reserved_bytes":
                 self.pool.shard_reserved_bytes(s, page_b) + sh_over,
                 "live_bytes":
                 self.pool.shard_live_bytes(s, page_b) + sh_over,
                 "page_table_shipped_bytes": self.n_local * bucket * 4,
                 "live_pages": self.pool.shard_live_pages(s)}
                for s in range(self.dp)]}

    def _sample_gauges(self) -> None:
        """End-of-step gauge sampling (host-side).  Skipped entirely
        under the null registry — gauges are the only instrumentation
        with per-step cost, so ``metrics=False`` pays zero."""
        m = self.metrics
        if not m.enabled:
            return
        m.gauge("serve_engine_steps",
                "scheduler steps taken").set(self.step_count)
        m.gauge("serve_queue_depth",
                "arrived requests waiting for a slot").set(self.pending)
        m.gauge("serve_lanes_active",
                "slots holding a live request").set(self.n_active)
        acct = self._cache_bytes()
        m.gauge("kv_cache_reserved_bytes",
                "cache bytes physically allocated").set(
                    acct["reserved_bytes"])
        m.gauge("kv_cache_live_bytes",
                "cache bytes addressable by live tokens").set(
                    acct["live_bytes"])
        if "page_table_shipped_bytes" in acct:
            m.gauge("page_table_shipped_bytes",
                    "bytes of the shipped [n_slots, bucket] int32 "
                    "page-table prefix").set(
                        acct["page_table_shipped_bytes"])
        if self.paged:
            m.gauge("page_pool_live_pages",
                    "pages mapped to live sequences").set(
                        self.pool.live_pages)
            m.gauge("page_pool_free_pages",
                    "pages on the free lists").set(self.pool.free_pages)
        for sh in range(self.dp):
            lo = sh * self.n_local
            lanes = self.slots[lo:lo + self.n_local]
            m.gauge("shard_lanes_active",
                    "decoding lanes on this shard", shard=sh).set(
                        sum(1 for s in lanes
                            if s is not None and s.state == "decoding"))
            m.gauge("shard_lanes_prefilling",
                    "prefilling lanes on this shard", shard=sh).set(
                        sum(1 for s in lanes
                            if s is not None and s.state == "prefilling"))
            if acct["shards"] is not None:
                e = acct["shards"][sh]
                m.gauge("shard_kv_cache_reserved_bytes",
                        "cache bytes physically allocated on this shard",
                        shard=sh).set(e["reserved_bytes"])
                m.gauge("shard_kv_cache_live_bytes",
                        "live cache bytes on this shard", shard=sh).set(
                            e["live_bytes"])
            if self.paged:
                m.gauge("shard_page_pool_live_pages",
                        "live pages on this shard", shard=sh).set(
                            self.pool.shard_live_pages(sh))
                m.gauge("shard_page_pool_free_pages",
                        "free pages on this shard", shard=sh).set(
                            self.pool.shard_free_pages(sh))

    def cache_report(self) -> Dict[str, Any]:
        """Cache accounting across all slots, on ONE byte basis: the
        config's actual dtypes (the lockstep ``ServeSession`` keeps the
        paper's fp16 Eq. 1 view; the engine reports deployable bytes).

        Always reports BOTH ``reserved_bytes`` (physically allocated) and
        ``live_bytes`` (addressable by live tokens right now).  The slab
        engine commits the worst case up front, so the two coincide there
        (checked against the actually-resident state arrays); the paged
        engine is the one whose live bytes track generated tokens.

        ``shards`` breaks both down per mesh shard (one entry on a single
        device); the per-shard entries always sum exactly to the totals —
        asserted in tests/test_paged_engine.py.  All byte figures come
        from :meth:`_cache_bytes`, the same source the per-step
        ``kv_cache_*`` gauges sample.
        """
        rep = serve_cache_report(self.cfg, self.swan, self.n_slots,
                                 self.max_seq)
        if self.api.init_paged_state is None:
            # recurrent-state families: no row-granular layout to page or
            # audit — keep the analytic Eq. 1 report (no shard breakdown)
            rep["reserved_bytes"] = rep["live_bytes"] = rep["bytes"]
            return rep
        acct = self._cache_bytes()
        rep["reserved_bytes"] = acct["reserved_bytes"]
        rep["live_bytes"] = acct["live_bytes"]
        rep["shards"] = acct["shards"]
        dense_phys = self._n_attn() * hc.dense_cache_bytes(
            self.cfg, self.n_slots, self.max_seq)
        if not self.paged:
            rep["bytes"] = acct["reserved_bytes"]
            if self.swan is not None:
                rep["dense_bytes"] = dense_phys
                rep["saving"] = 1.0 - rep["bytes"] / dense_phys
            return rep
        rep["mode"] += "+paged"
        rep["slab_bytes"] = self._n_attn() * hc.cache_bytes(
            self.cfg, self.swan, self.n_slots, self.max_seq)
        rep["bytes"] = acct["live_bytes"]
        rep["dense_bytes"] = dense_phys
        rep["saving"] = 1.0 - acct["live_bytes"] / dense_phys
        rep.update(page_size=self.pool.page_size, n_pages=self.pool.n_pages,
                   live_pages=self.pool.live_pages)
        rep["page_table_shipped_bytes"] = acct["page_table_shipped_bytes"]
        return rep


def _dp_index(mesh, dpx):
    """This device's linear index along the mesh's data axes (inside
    shard_map)."""
    idx = jax.lax.axis_index(dpx[0])
    for a in dpx[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx
