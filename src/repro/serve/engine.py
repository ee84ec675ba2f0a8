"""Serving engine: continuous batching + fixed-batch policies, with
energy-per-token accounting and a paged/slotted KV cache choice.

``ServeEngine`` owns the jitted prefill/decode programs and the KV cache
(``serve.cache``); on top of that single engine sit two admission
policies (``serve.scheduler``):

  * ``continuous`` — Orca/vLLM-style iteration-level scheduling: slots
    refill from the queue between decode steps, requests early-exit on
    EOS and free their cache row immediately;
  * ``fixed``      — classic fixed-batch serving (admit a full batch,
    drain it, admit the next) — the baseline the serve benchmark
    measures continuous batching against.

The decode hot path (model mode) is built around three mechanisms:

  * **cache layouts** — ``cache="slotted"`` keeps the dense
    ``(n_slots, max_len)`` row pool (the reference path);
    ``cache="paged"`` switches to ``serve.cache.PagedKVCache``:
    fixed-size KV blocks in a shared pool addressed by per-slot block
    tables, with decode attention walking only the blocks a slot owns
    (``models.attention.decode_attention`` paged path →
    ``kernels.ops.paged_decode_attention``; the gathered table width is
    bucketed to the longest live slot, so short batches never pay
    ``max_len``).
  * **batched prefill** — newly admitted requests prefill as one padded
    batch per prompt-length bucket (one jitted program per bucket,
    batch padded to ``n_slots`` so admission count never retraces);
    first tokens arrive in a single host fetch instead of one
    ``.item()`` per request.
  * **fused decode runs** — when the scheduler can prove no slot can
    finish for the next ``k`` steps (length budgets are known; EOS makes
    ``k=1``), the engine dispatches ``k`` decode steps back-to-back with
    the token stream chained **on device** and drains all ``k`` outputs
    in one batched ``np.asarray`` fetch afterwards — scheduler
    bookkeeping overlaps device compute instead of blocking every token.

Two scheduler modes share the loop (``sched=``):

  * ``phased``  — a newly admitted request's WHOLE prompt prefills at
    admission; long prompts stall every decoding slot for the full
    prefill (the TTFT/TPOT cliff the chunked mode removes);
  * ``chunked`` — iteration-level scheduling proper: each loop
    iteration runs at most one ``chunk_tokens`` prefill slice per
    mid-prefill slot (one program call each), then one decode step for
    every fully-prefilled slot. Chunk boundaries are block-aligned, so
    every chunk after the first reuses the suffix-prefill program
    (``prefix_kv`` gathered from the slot's own blocks). Admission
    reserves only prompt+1 blocks (optimistic); decode-time growth that
    hits ``CacheOOM`` preempts the youngest other request — its blocks
    free, it re-queues at the front, and it resumes later by
    recompute-from-prompt (``Scheduler.preempt``): the original prompt
    prefills again chunk by chunk, then the already-emitted tail
    REPLAYS through the decode program as forced inputs
    (``Request.n_replay`` / ``Slot.replay``) — decode built that KV the
    first time, and prefill's attention numerics are not bit-equal to
    decode's, so replay is what keeps a resumed stream bit-identical.
    Requires the paged cache. Greedy argmax streams are bit-identical
    to phased: chunked prefill computes the same causal attention in
    block-aligned slices, and every KV row is built by the same program
    phased used for it. Stacks with mamba mixers (ssm/hybrid) carry
    their conv and SSM state from chunk to chunk: a chunk after the
    first gathers its slot's state rows into the program, and the
    decode step leaves the rows of slots that are not decoding as they
    are.

Energy: the engine reads its ``PowerMethod`` list synchronously at every
step-window boundary, so each prefill/decode window is bracketed by
samples and ``repro.core.metrics.attribute_energy`` integrates exactly
over it — yielding Wh/token and Wh/request per served request (the
MLPerf-Power figure of merit). Fused windows credit each active rid once
per micro-step, keeping the attribution exact.

Spans and counters: each pass of the serve loop runs inside
``jax.profiler.TraceAnnotation("serve.iter")`` with the children
``serve.admit`` (shedding, refill, reservation, chunk start),
``serve.prefill`` (one prefill program call: host arrays, dispatch,
insert), ``serve.prefill.fetch``, ``serve.decode`` (growth, block
tables, dispatch of the window's steps), ``serve.decode.fetch`` (the
drain), ``serve.emit`` (token bookkeeping) and ``serve.wait`` (the sleep
until the next arrival); the first call of each program key runs inside
``serve.first_call``, so a compile inside a traced window shows by
name. A prefill or decode span's ``step`` kwarg is the index of its
``StepRecord``, whose counters (``iter``, ``rows``, ``width``,
``used``) the span carries too. On stacks with mamba mixers a prefill
span carries ``carried`` (rows that resume a recurrent state); on
stacks with dropless expert layers prefill and decode spans carry
``moe_rows`` (grouped-matmul buffer rows the call's programs hold,
summed over expert layers), and the ``serve.emit`` span after a fetch
carries ``moe_local``: the real-token routed choices that landed on
held experts, counted on the device by every program since the last
fetch and fetched with it (``moe_local_prefill``: the prefill
programs' part). Only ints go in kwargs, and no span adds
a device sync: with the profiler off a span costs a context entry.

``serve_step`` (single-token decode against a full KV cache) is what the
``decode_*`` / ``long_*`` dry-run shapes lower. ``BatchedServer`` remains
as the thin fixed-batch wrapper the examples/tests drive.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.metrics import (
    ServeSummary, attribute_energy, serve_summary,
)
from repro.core.runner import StragglerWatchdog
from repro.models import blocks, lm
from repro.models.moe import route_rows
from repro.serve.cache import (
    CacheOOM, PagedKVCache, copy_blocks, grow_caches,
    insert_paged_prefill, insert_rows, slotted_cache,
)
from repro.serve.requests import Request, RequestResult
from repro.serve.scheduler import Scheduler, Slot, StepRecord

Params = Any


def make_prefill_fn(c: ModelConfig, impl: str = "repeat"):
    def prefill_step(params, tokens, extras):
        logits, caches, enc_kv = lm.prefill(
            c, params, tokens,
            patch_embeds=extras.get("patch_embeds"),
            enc_frames=extras.get("enc_frames"), impl=impl)
        return logits, caches, enc_kv
    return prefill_step


def make_decode_fn(c: ModelConfig, impl: str = "grouped"):
    def serve_step(params, token, caches, pos, enc_kv=None):
        return lm.decode_step(c, params, token, caches, pos,
                              enc_kv=enc_kv, impl=impl)
    return serve_step


def paged_kernel() -> tuple[str, bool]:
    """``(impl, interpret)`` of the paged attention ops, chosen from the
    platform: the Pallas kernels on a TPU, the XLA reference
    (``kernels.ref``) elsewhere. Interpret mode is for tests, which
    steer this function themselves."""
    return ("pallas" if jax.default_backend() == "tpu" else "xla"), False


@dataclass
class GenerationResult:
    tokens: Any
    steps: int
    prefill_s: float
    decode_s: float

    @property
    def decode_tokens_per_s(self) -> float:
        n = self.tokens.shape[0] * self.steps
        return n / max(self.decode_s, 1e-9)


@dataclass
class ServeRunResult:
    """Outcome of one ``ServeEngine.serve`` run."""

    results: list                 # RequestResult, completion order
    steps: list                   # StepRecord log (energy attribution)
    sample_ts: list               # synchronous power sample times
    sample_ws: list               # total watts at each sample
    summary: ServeSummary
    straggler_events: list = field(default_factory=list)

    def by_rid(self) -> dict:
        return {r.rid: r for r in self.results}


class ServeEngine:
    """Shared serving engine: jitted prefill/decode + slotted/paged KV.

    Model mode (the default): pass ``(c, params)`` — the engine jits
    prefill/decode once and allocates the cache pool on first use.
    ``max_len`` is the TOTAL per-slot capacity (prompt + generated
    tokens). ``cache`` selects the KV layout (``"slotted"`` dense rows /
    ``"paged"`` block tables, see module docstring); ``decode_window``
    caps how many decode steps a fused run may keep in flight (1
    restores the legacy sync-every-token loop).

    Scripted mode (unit tests): pass ``prefill_fn``/``decode_fn`` —
    host-side callables with no device work:

      prefill_fn(slot: int, prompt: np.ndarray) -> int   first token
      decode_fn(tokens (S,), positions (S,), active (S,) bool) -> (S,)

    plus an optional fake ``clock``/``sleep_fn`` pair, which makes the
    energy accounting exactly computable in tests. Scripted mode keeps
    the legacy one-request-prefill / one-step-decode loop so the exact
    step windows the energy tests assert against are unchanged.
    """

    def __init__(self, c: Optional[ModelConfig] = None,
                 params: Params = None, *,
                 n_slots: int = 4, max_len: int = 256,
                 impl_prefill: str = "repeat", impl_decode: str = "grouped",
                 donate: bool = True,
                 cache: str = "slotted", block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_dtype: str = "fp32",
                 decode_window: int = 8,
                 sched: str = "phased", chunk_tokens: int = 32,
                 prefill_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep_fn: Optional[Callable[[float], None]] = None,
                 power_methods: Sequence = (),
                 watchdog: Optional[StragglerWatchdog] = None):
        assert cache in ("slotted", "paged"), cache
        assert sched in ("phased", "chunked"), sched
        assert not prefix_cache or cache == "paged", (
            "prefix caching shares KV blocks — requires the paged cache")
        assert kv_dtype in ("fp32", "int8"), kv_dtype
        assert kv_dtype == "fp32" or cache == "paged", (
            "int8 KV quantizes pool blocks — requires the paged cache")
        self.c, self.params = c, params
        self.n_slots, self.max_len = n_slots, max_len
        self.cache_kind = cache
        self.block_size = block_size
        self._n_blocks = n_blocks
        self.prefix_cache = prefix_cache
        #: "fp32" = unquantized pool at the model's native cache dtype;
        #: "int8" = quantized blocks + per-(block, head) scales
        self.kv_dtype = kv_dtype
        self.decode_window = max(int(decode_window), 1)
        #: default scheduler mode for serve(): "phased" keeps the
        #: admission-wave prefill; "chunked" interleaves chunk_tokens
        #: prefill slices with decode steps (iteration-level scheduling)
        self.sched = sched
        self.chunk_tokens = int(chunk_tokens)
        self.preemptions = 0          # preempt events in the last serve()
        self._iter = 0                # the serve loop's iteration
        self.shed = 0                 # requests shed in the last serve()
        self.injected_faults = 0      # slot faults fired in the last serve()
        self.paged_impl, self.paged_interpret = paged_kernel()
        self.impl_prefill = impl_prefill
        self.impl_decode, self.donate = impl_decode, donate
        self.clock = clock
        self.sleep_fn = sleep_fn or time.sleep
        self.power_methods = list(power_methods)
        self.watchdog = watchdog
        self._decode_idx = 0
        self._scripted = prefill_fn is not None
        if self._scripted:
            self._slot_prefill = prefill_fn
            self._slot_decode = decode_fn
        else:
            assert c is not None and params is not None
            # legacy fixed-batch generate() programs
            self._prefill = jax.jit(make_prefill_fn(c, impl_prefill))
            decode = make_decode_fn(c, impl_decode)
            self._decode = jax.jit(decode,
                                   donate_argnums=(2,) if donate else ())
            self._grow = jax.jit(grow_caches, static_argnums=(1,))
            #: mamba mixers carry recurrent state between prefill chunks
            self._carries = c.family in ("ssm", "hybrid")
            #: dropless expert layers count the choices they route
            self._counts = blocks.routes_share(c)
            #: the programs need each row's real length (padding steps
            #: no recurrence and routes to no expert)
            self._rowwise = self._carries or self._counts
            self._moe_layers = sum(map(c.is_moe_layer, range(c.n_layers)))
            #: device counts not yet fetched: (prefill?, count)
            self._routed: list = []
            # serve programs: batched prefill returning per-row argmax
            # first tokens; single-step decode returning next tokens so
            # fused runs chain the token stream on device
            def serve_prefill(params, tokens, last_pos):
                out = lm.prefill(c, params, tokens, impl=impl_prefill,
                                 last_pos=last_pos, **self._row_kw(last_pos))
                first = jnp.argmax(out[0][:, -1], -1).astype(jnp.int32)
                return (first, out[1]) + tuple(out[3:])
            self._serve_prefill = jax.jit(serve_prefill)

            def serve_step(params, tok, caches, pos):
                logits, caches = lm.decode_step(c, params, tok[:, None],
                                                caches, pos, impl=impl_decode)
                return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), caches
            self._serve_step = jax.jit(
                serve_step, donate_argnums=(2,) if donate else ())
            self._paged_steps: dict = {}
            self._prefix_prefills: dict = {}
            self._cold_prefills: dict = {}
            self.prefix_stats: dict = self._blank_prefix_stats()
            self._paged: Optional[PagedKVCache] = None
            #: admission-control ledger: slot -> worst-case block demand
            #: (prompt + full token budget). Admission only proceeds when
            #: the pool can cover every active slot's remaining demand,
            #: so decode-time ``ensure`` growth can never hit CacheOOM.
            self._slot_cap: dict[int, int] = {}
            #: free-block count snapshotted when admission last deferred
            #: the queue head; admission only retries once it changes
            self._defer_free_blocks: Optional[int] = None
            self.caches: Params = None   # allocated on first serve()

    # ------------------------------------------------------------------
    # Model-backed cache + program construction
    # ------------------------------------------------------------------

    def _row_kw(self, last) -> dict:
        """What a prefill program passes ``lm.prefill`` beside its
        tokens: each row's real length (``last + 1``; pad rows have
        ``last == -1``) and the routed count, where the stack needs
        them."""
        kw: dict = {}
        if self._rowwise:
            kw["valid_len"] = last + 1
        if self._counts:
            kw["return_routed"] = True
        return kw

    def _keep_routed(self, fn: Callable, prefill: bool) -> Callable:
        """``fn`` whose program returns a routed count last: the count
        stays on the device, kept until the next fetch, and the other
        outputs are returned."""
        if not self._counts:
            return fn

        def call(*args):
            *out, n = fn(*args)
            self._routed.append((prefill, n))
            return tuple(out)
        return call

    def _fetch_routed(self) -> dict:
        """The counts kept since the last fetch, summed, as the
        ``serve.emit`` kwargs; called right after a fetch the engine
        makes anyway, when the programs that counted have run."""
        if not self._counts:
            return {}
        got = jax.device_get([n for _p, n in self._routed])
        kw = {"moe_local": int(sum(got)),
              "moe_local_prefill": int(sum(
                  g for (p, _n), g in zip(self._routed, got) if p))}
        self._routed = []
        return kw

    def _moe_rows(self, tokens: int) -> dict:
        """``moe_rows`` span kwarg: grouped-matmul buffer rows for
        ``tokens`` token rows, over every expert layer."""
        if not self._counts:
            return {}
        return {"moe_rows": tokens * route_rows(self.c) * self._moe_layers}

    def _ensure_cache(self):
        if self.caches is not None:
            return
        assert self.c.family not in ("encdec", "vlm"), (
            "continuous batching currently covers decoder-only "
            "families (dense/moe/ssm/hybrid); encdec/vlm need "
            "per-request side inputs — use the fixed-batch policy")
        if self.cache_kind == "paged":
            self._paged = PagedKVCache(self.c, self.n_slots, self.max_len,
                                       self.params,
                                       block_size=self.block_size,
                                       n_blocks=self._n_blocks,
                                       kv_dtype=self.kv_dtype)
            if self.prefix_cache:
                assert self.c.family not in ("ssm", "hybrid"), (
                    "prefix caching skips prefix recompute — impossible "
                    "for mamba recurrences, which must run through the "
                    "whole sequence (attention-only families)")
                self._paged.enable_prefix_cache()
            # the engine takes ownership of the device tree: the jitted
            # serve programs donate it in place, which would leave the
            # PagedKVCache attribute pointing at deleted buffers — clear
            # it so a stale read fails loudly instead
            self.caches = self._paged.caches
            self._paged.caches = None
        else:
            self.caches = slotted_cache(self.c, self.n_slots, self.max_len,
                                        self.params)

    def _paged_step_fn(self, nb: int):
        """Decode program gathering ``nb`` block-table columns (static —
        one compiled program per bucket, reused across steps). Stacks with
        per-row state also take ``live`` (n_slots,) bool, the rows that
        decode a real token (the scheduler's active mask); a caller that
        gives none (a warm-up) runs the same program with every row
        parked."""
        fn = self._paged_steps.get(nb)
        if fn is None:
            c = self.c

            def step(params, tok, caches, pos, tables, live=None):
                kw: dict = {}
                if live is not None:
                    kw["live"] = live
                if self._counts:
                    kw["return_routed"] = True
                out = lm.decode_step(
                    c, params, tok[:, None], caches, pos,
                    impl=self.impl_decode, block_tables=tables,
                    n_kv_blocks=nb, paged_impl=self.paged_impl,
                    paged_interpret=self.paged_interpret, **kw)
                return (jnp.argmax(out[0][:, -1], -1).astype(jnp.int32),
                        ) + tuple(out[1:])

            fn = self._keep_routed(self._first_call(
                jax.jit(step, donate_argnums=(2,) if self.donate else ()),
                nb=nb), prefill=False)
            if self._rowwise:
                program, parked = fn, jnp.zeros((self.n_slots,), bool)

                def fn(params, tok, caches, pos, tables, live=parked):
                    return program(params, tok, caches, pos, tables, live)
            self._paged_steps[nb] = fn
        return fn

    def _first_call(self, fn: Callable, **key) -> Callable:
        """``fn`` whose first call, where it traces and compiles (or
        loads from the compile cache), runs inside ``serve.first_call``
        with the program's key as kwargs."""
        called = False

        def call(*args):
            nonlocal called
            if called:
                return fn(*args)
            called = True
            with TraceAnnotation("serve.first_call", **key):
                return fn(*args)
        return call

    def _cold_prefill_fn(self, bucket: int):
        """The batched prefill program at one prompt bucket (no prefix)."""
        fn = self._cold_prefills.get(bucket)
        if fn is None:
            fn = self._keep_routed(self._first_call(
                self._serve_prefill, bucket=bucket, npre=0), prefill=True)
            self._cold_prefills[bucket] = fn
        return fn

    def _prefix_prefill_fn(self, bucket: int, npre: int):
        """Suffix-prefill program for prompts whose first ``npre`` blocks
        hit the prefix index: the ``bucket``-padded suffix attends
        against the slot's prefix blocks IN the pool via the paged
        prefill kernel (``kernels.ops.paged_prefill_attention`` — the
        per-row block table rides into the program; no dense prefix-KV
        gather is ever materialized), and suffix cache rows come back.
        One compiled program per (suffix bucket, prefix depth) pair. The
        pool is read, never donated — the suffix rows scatter in via
        ``insert_paged_prefill`` afterwards, exactly like a cold
        prefill. Stacks with mamba mixers also take ``slot_ids``, the
        pool rows whose conv and SSM state each row resumes from."""
        key = (bucket, npre)
        fn = self._prefix_prefills.get(key)
        if fn is None:
            c, bs = self.c, self.block_size
            impl = self.impl_prefill

            def prefill_hit(params, caches, tokens, last, pre_blocks,
                            slot_ids=None):
                out = lm.prefill(
                    c, params, tokens, impl=impl, last_pos=last,
                    paged_prefix=caches, paged_tables=pre_blocks,
                    pos_offset=npre * bs, paged_impl=self.paged_impl,
                    paged_interpret=self.paged_interpret,
                    carry_slots=slot_ids, **self._row_kw(last))
                first = jnp.argmax(out[0][:, -1], -1).astype(jnp.int32)
                return (first, out[1]) + tuple(out[3:])

            fn = self._keep_routed(self._first_call(
                jax.jit(prefill_hit), bucket=bucket, npre=npre), prefill=True)
            self._prefix_prefills[key] = fn
        return fn

    @staticmethod
    def _blank_prefix_stats() -> dict:
        return {"hit_requests": 0, "miss_requests": 0,
                "reused_blocks": 0, "registered_blocks": 0}

    def _nb_bucket(self, n: int) -> int:
        """Static gather width for ``n`` live blocks: the next power of
        two, capped at ``max_blocks`` — a handful of compiled programs
        covers every live-length mix."""
        cap = self._paged.max_blocks
        b = 1
        while b < n and b < cap:
            b *= 2
        return min(b, cap)

    def _prompt_bucket(self, n: int) -> int:
        """Prompt-length bucket for batched prefill: the next
        ``block_size`` multiple — causal masking hides the pad tokens'
        KV until decode overwrites it, and mamba mixers step over the
        padding (``valid_len``), so coarse buckets are free and cut trace
        count. Partial paged blocks are zero-padded by
        ``insert_paged_rows``.
        """
        b = -(-n // self.block_size) * self.block_size
        return min(max(b, self.block_size), self.max_len)

    # ------------------------------------------------------------------
    # Paged admission control (CacheOOM -> deferral, not a crash)
    # ------------------------------------------------------------------

    def _free_paged_slot(self, slot_index: int) -> None:
        self._paged.free(slot_index)
        self._slot_cap.pop(slot_index, None)

    def _paged_headroom(self) -> int:
        """Free blocks not yet spoken for by active slots' worst-case
        growth (their cap minus what they already own). Blocks pinned
        only by the prefix index count as available: ``ensure`` reclaims
        them LRU-first when the free list runs dry, so a warm index can
        never starve admission."""
        reserved = sum(max(0, cap - self._paged.owned(s))
                       for s, cap in self._slot_cap.items())
        return self._paged.available_blocks - reserved

    def _admit_paged(self, sched: Scheduler, admitted: list,
                     results=None, chunked: bool = False) -> list:
        """Reserve pool blocks for new admissions; defer (phased) or
        preempt (chunked) when the pool cannot cover them.

        Each admitted request reserves its worst-case block demand
        (prompt + full ``max_new_tokens`` budget); when the pool's
        unreserved headroom cannot cover the next request, that request
        — and everything behind it, preserving FIFO — goes back to the
        queue front and waits for active slots to finish and free
        blocks. Because an empty pool always covers one full slot
        (PagedKVCache asserts so), the head request always admits
        eventually: deferral, never deadlock, never ``CacheOOM``.

        Under the chunked scheduler the reservation is OPTIMISTIC —
        prompt + first token only — and a shortfall PREEMPTS strictly
        younger running slots instead of only deferring: the queue head
        is older than they are, so it reclaims their blocks and they
        resume later (recompute + replay). This is what turns phased's
        multi-hundred-millisecond admission stalls behind long-lived
        generations into a bounded eviction cost, and what lets an
        oversubscribed pool run cells the phased scheduler can only
        defer. Requeue order keeps FIFO: the not-yet-prefilled
        admission tail unadmits first, then victims (older than the
        tail) land ahead of it at the queue front.

        A deferral snapshots ``free_blocks``; the serve loop skips the
        refill/unadmit churn — and stops treating the head as pending
        for the decode fusion check — until that count changes (blocks
        only move at window edges, so no retry can succeed earlier).
        """
        ok = []
        for i, slot in enumerate(admitted):
            req = slot.request
            if chunked:
                cap = -(-(req.prompt_len + 1) // self.block_size)
            else:
                cap = -(-(req.prompt_len + req.max_new_tokens)
                        // self.block_size)
            if cap > self._paged_headroom():
                if not chunked:
                    for later in reversed(admitted[i:]):
                        sched.unadmit(later)
                    self._defer_free_blocks = self._paged.available_blocks
                    break
                # chunked: free the unprefillled tail's reservations,
                # then evict strictly younger running slots until the
                # head fits ( _pick_victim's strict-younger rule also
                # keeps ``slot`` itself off the victim list)
                for later in reversed(admitted[i + 1:]):
                    sched.unadmit(later)
                me = (req.arrival_s, req.rid)
                while cap > self._paged_headroom():
                    victim = self._pick_victim(sched, me)
                    if victim is None:
                        break
                    self._preempt_slot(sched, victim, results)
                if cap > self._paged_headroom():
                    sched.unadmit(slot)
                    self._defer_free_blocks = self._paged.available_blocks
                    break
                self._slot_cap[slot.index] = cap
                ok.append(slot)
                # the tail re-admits on the next loop iteration, behind
                # any just-preempted (older) victims
                break
            self._slot_cap[slot.index] = cap
            ok.append(slot)
        return ok

    def _admission_blocked(self) -> bool:
        """True while a deferred queue head cannot possibly admit: the
        pool's free-block count hasn't moved since the deferral."""
        snap = getattr(self, "_defer_free_blocks", None)
        return (snap is not None and self._paged is not None
                and self._paged.available_blocks == snap)

    # ------------------------------------------------------------------
    # Block-granular preemption (chunked scheduler)
    # ------------------------------------------------------------------

    def _ensure_with_preempt(self, sched: Scheduler, slot: Slot,
                             n_tokens: int, results) -> bool:
        """Grow ``slot``'s pool to ``n_tokens`` rows, preempting younger
        requests on ``CacheOOM``. Returns True once the growth lands;
        False when the slot itself was preempted instead.

        Victims are STRICTLY YOUNGER than the beneficiary (arrival, then
        rid): when every other active request is older, the beneficiary
        defers ITSELF back to the queue front rather than evict an older
        request — the oldest active request can therefore always preempt
        its way to completion, so an oversubscribed pool degrades to
        FIFO-ordered service instead of livelocking on mutual eviction.
        """
        req = slot.request
        me = (req.arrival_s, req.rid)
        while True:
            try:
                self._paged.ensure(slot.index, n_tokens)
                return True
            except CacheOOM:
                victim = self._pick_victim(sched, me)
                if victim is None:
                    self._preempt_slot(sched, slot, results)
                    return False
                self._preempt_slot(sched, victim, results)

    def _pick_victim(self, sched: Scheduler,
                     me: tuple) -> Optional[Slot]:
        """Youngest active request strictly younger than ``me``; ties
        (same arrival) evict the fewest-blocks slot — the cheapest
        recompute-from-prompt."""
        cands = [s for s in sched.slots if s.active
                 and (s.request.arrival_s, s.request.rid) > me]
        if not cands:
            return None
        return max(cands, key=lambda s: (s.request.arrival_s,
                                         -self._paged.owned(s.index),
                                         s.request.rid))

    def _preempt_slot(self, sched: Scheduler, victim: Slot,
                      results) -> int:
        """Evict ``victim``: scheduler state first (the resume request
        captures the emitted stream), then reclaim its blocks. Returns
        the number of blocks actually returned to the free list (shared
        blocks a prefix pin or another slot still references stay)."""
        idx = victim.index
        rid = victim.request.rid
        sched.preempt(victim, results[rid].tokens)
        freed = self._paged.free(idx)
        self._slot_cap.pop(idx, None)
        # blocks moved — a headroom-deferred queue head may now retry
        self._defer_free_blocks = None
        self.preemptions += 1
        return freed

    # ------------------------------------------------------------------
    # Model-backed serve phases
    # ------------------------------------------------------------------

    def _model_prefill_admitted(self, sched: Scheduler, admitted, results,
                                steps, ts, ws):
        """Prefill newly admitted slots as one padded batch per
        (suffix-bucket, prefix-depth) group; one host fetch returns
        every first token.

        With prefix caching on, each prompt is first matched against the
        prefix index: a hit of ``npre`` full blocks adopts those shared
        pool blocks (refcounted, never copied) and prefills ONLY the
        remaining suffix — the jitted program gathers the prefix K/V out
        of the pool and attends across [prefix ++ suffix]. Every prompt
        then registers its own full blocks so later requests can hit
        them. Misses (npre=0) take the exact cold path."""
        use_prefix = (self.prefix_cache and self.cache_kind == "paged")
        groups: dict[tuple, list] = {}
        for slot in admitted:
            pre: list = []
            if use_prefix:
                pre = self._paged.prefix_match(
                    [int(t) for t in slot.request.prompt])
            npre = len(pre)
            suffix = slot.request.prompt_len - npre * self.block_size
            bucket = self._prompt_bucket(suffix)
            groups.setdefault((bucket, npre), []).append((slot, pre))
        for (bucket, npre), entries in sorted(groups.items()):
            kp = self.n_slots       # fixed batch: admission never retraces
            pre_len = npre * self.block_size
            used = sum(s.request.prompt_len for s, _pre in entries) \
                - pre_len * len(entries)
            with TraceAnnotation("serve.prefill", step=len(steps),
                                 iter=self._iter, rows=kp, bucket=bucket,
                                 npre=npre, used=used,
                                 **self._moe_rows(kp * bucket)):
                t0 = self.clock()
                self._sample_power(ts, ws)   # bracket the prefill window
                tokens = np.zeros((kp, bucket), np.int32)
                last = np.full((kp,), -1, np.int32)   # pad rows: no tokens
                # pad rows: dropped
                slot_ids = np.full((kp,), self.n_slots, np.int32)
                # pad rows gather the trash block — harmless, never read back
                pre_blocks = np.zeros((kp, npre), np.int32)
                for i, (slot, pre) in enumerate(entries):
                    plen = slot.request.prompt_len
                    prompt = np.asarray(slot.request.prompt, np.int32)
                    tokens[i, :plen - pre_len] = prompt[pre_len:]
                    last[i] = plen - pre_len - 1
                    slot_ids[i] = slot.index
                    if npre:
                        pre_blocks[i] = pre
                if npre:
                    first, rows = self._prefix_prefill_fn(bucket, npre)(
                        self.params, self.caches, jnp.asarray(tokens),
                        jnp.asarray(last), jnp.asarray(pre_blocks))
                else:
                    first, rows = self._cold_prefill_fn(bucket)(
                        self.params, jnp.asarray(tokens), jnp.asarray(last))
                if self.cache_kind == "paged":
                    nbk = -(-bucket // self.block_size)
                    blocks = np.full((kp, nbk), self._paged.n_blocks,
                                     np.int32)
                    for i, (slot, pre) in enumerate(entries):
                        plen = slot.request.prompt_len
                        if npre:
                            self._paged.adopt(slot.index, pre)
                        self._paged.ensure(slot.index, plen)
                        own = self._paged.block_ids(slot.index, plen)[npre:]
                        blocks[i, :len(own)] = own
                    self.caches = insert_paged_prefill(
                        self.caches, rows, jnp.asarray(blocks),
                        jnp.asarray(slot_ids), block_size=self.block_size)
                    if use_prefix:
                        st = self.prefix_stats
                        st["hit_requests" if npre else
                           "miss_requests"] += len(entries)
                        st["reused_blocks"] += npre * len(entries)
                        for slot, _pre in entries:
                            st["registered_blocks"] += \
                                self._paged.prefix_register(
                                    slot.index,
                                    [int(t) for t in slot.request.prompt])
                else:
                    self.caches = insert_rows(self.caches, rows,
                                              jnp.asarray(slot_ids))
            with TraceAnnotation("serve.prefill.fetch"):
                first_np = np.asarray(first)  # single batched device fetch
                routed = self._fetch_routed()
            t1 = self.clock()
            self._sample_power(ts, ws)
            rids = tuple(s.request.rid for s, _pre in entries)
            slots = [s for s, _pre in entries]
            steps.append(StepRecord("prefill", t0, t1, rids, len(rids),
                                    iter=self._iter, rows=kp, width=bucket,
                                    used=used))
            with TraceAnnotation("serve.emit", **routed):
                for i, slot in enumerate(slots):
                    res = results[slot.request.rid]
                    res.slot = slot.index
                    res.admitted_s, res.first_token_s = t0, t1
                    tok = int(first_np[i])
                    res.tokens.append(tok)
                    slot_index = slot.index
                    reason = sched.record_token(slot, tok)
                    if reason is not None:
                        res.finish_s, res.finish_reason = t1, reason
                        if self._paged is not None:
                            self._free_paged_slot(slot_index)

    def _start_chunked(self, admitted, results) -> None:
        """Begin chunked prefill for newly admitted slots: rewind
        ``prefill_pos`` (refill set it to ``prompt_len``, the phased
        default) to the prefix-match depth, adopting shared prefix
        blocks when the index hits. The chunk executor advances from
        there, one block-aligned slice per loop iteration."""
        t_admit = self.clock()
        for slot in admitted:
            req = slot.request
            res = results[req.rid]
            res.slot = slot.index
            # a preemption-resume keeps its original admission stamp:
            # TTFT measures first service, not re-service
            if res.admitted_s == 0.0:
                res.admitted_s = t_admit
            pre: list = []
            if self.prefix_cache:
                pre = self._paged.prefix_match(
                    [int(t) for t in req.prompt])
                if req.n_replay:
                    # a resume rebuilds its emitted tail via decode
                    # replay — adoption must stop short of the replay
                    # region, leaving >= 1 original-prompt token so the
                    # last prefill chunk is never empty
                    pre = pre[:max(slot.prefill_target - 1, 0)
                              // self.block_size]
                st = self.prefix_stats
                st["hit_requests" if pre else "miss_requests"] += 1
                st["reused_blocks"] += len(pre)
            if pre:
                self._paged.adopt(slot.index, pre)
            slot.prefill_pos = len(pre) * self.block_size
            slot.pos = slot.prefill_pos   # KV rows landed so far

    def _model_prefill_chunks(self, sched: Scheduler, results, steps,
                              ts, ws, chunk_tokens: int) -> None:
        """Run ONE ``chunk_tokens`` prefill slice for every mid-prefill
        slot — the chunked scheduler's per-iteration prefill quantum —
        as one program call per slot, in (suffix-bucket, prefix-depth)
        order.

        Each call carries its slot's row alone: a batch padded to the
        slot count would run the whole model over rows that hold no
        token, and a (bucket, depth) group seldom holds more than one
        slot. The programs stay keyed by (bucket, depth), so the set a
        warm-up reaches is the set a run calls.

        Chunk ``j`` is just a suffix prefill against the slot's own
        first ``prefill_pos / block_size`` blocks, so it reuses the
        prefix-cache program verbatim (``_prefix_prefill_fn``). A
        non-final chunk writes KV only — its argmax is discarded (the
        slice's last token is not the prompt's last). The final chunk
        emits the first token exactly like phased prefill and registers
        the full prompt with the prefix index. Pool growth for a chunk
        may preempt a younger slot — possibly one in this very wave,
        which then drops out before its call."""
        slots = [s for s in sched.slots if s.prefilling]
        # grow pools oldest-first so preemption flows old -> young
        for slot in sorted(slots,
                           key=lambda s: (s.request.arrival_s
                                          if s.request else 0.0,
                                          s.index)):
            if not slot.prefilling:
                continue   # preempted by an older slot's growth
            end = min(slot.prefill_pos + chunk_tokens,
                      slot.prefill_target)
            self._ensure_with_preempt(sched, slot, end, results)
        chunks = []
        for slot in slots:
            if not slot.prefilling:
                continue
            start = slot.prefill_pos
            end = min(start + chunk_tokens, slot.prefill_target)
            chunks.append((self._prompt_bucket(end - start),
                           start // self.block_size, slot, start, end))
        chunks.sort(key=lambda e: e[:2])   # stable: slot order kept
        for bucket, npre, slot, start, end in chunks:
            self._prefill_chunk(sched, results, steps, ts, ws,
                                slot, start, end, bucket, npre)

    def _prefill_chunk(self, sched: Scheduler, results, steps, ts, ws,
                       slot: Slot, start: int, end: int, bucket: int,
                       npre: int) -> None:
        """One slot's prompt slice ``[start, end)`` as one batch-1 call of
        the (``bucket``, ``npre``) program, its rows scattered into the
        slot's blocks; the final slice emits the first token."""
        used = end - start
        kw = self._moe_rows(bucket)
        if self._carries:
            kw["carried"] = int(npre > 0)
        with TraceAnnotation("serve.prefill", step=len(steps),
                             iter=self._iter, rows=1, bucket=bucket,
                             npre=npre, used=used, **kw):
            t0 = self.clock()
            self._sample_power(ts, ws)   # bracket the chunk window
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :used] = np.asarray(slot.request.prompt,
                                          np.int32)[start:end]
            last = jnp.asarray([used - 1], jnp.int32)
            slot_ids = jnp.asarray([slot.index], jnp.int32)
            if npre:
                pre_blocks = self._paged.block_ids(
                    slot.index, npre * self.block_size)
                # a resumed recurrence gathers its slot's state rows
                carry = (slot_ids,) if self._carries else ()
                first, rows = self._prefix_prefill_fn(bucket, npre)(
                    self.params, self.caches, jnp.asarray(tokens), last,
                    jnp.asarray(pre_blocks[None]), *carry)
            else:
                first, rows = self._cold_prefill_fn(bucket)(
                    self.params, jnp.asarray(tokens), last)
            nbk = -(-bucket // self.block_size)
            blocks = np.full((1, nbk), self._paged.n_blocks, np.int32)
            own = self._paged.block_ids(slot.index, end)[npre:]
            blocks[0, :len(own)] = own
            self.caches = insert_paged_prefill(
                self.caches, rows, jnp.asarray(blocks), slot_ids,
                block_size=self.block_size)
        req = slot.request
        final = end == slot.prefill_target
        emitting = final and not req.n_replay
        routed: dict = {}
        if emitting:
            with TraceAnnotation("serve.prefill.fetch"):
                tok = int(np.asarray(first)[0])
                routed = self._fetch_routed()
        t1 = self.clock()
        self._sample_power(ts, ws)
        # n_tokens counts only a first token actually emitted, keeping
        # the credited-token accounting exact
        steps.append(StepRecord("prefill", t0, t1, (req.rid,),
                                int(emitting), fetched=emitting,
                                iter=self._iter, rows=1, width=bucket,
                                used=used))
        with TraceAnnotation("serve.emit", **routed):
            slot.prefill_pos = end
            slot.pos = end
            if not final:
                return
            if req.n_replay:
                # resume: the original prompt is back in cache; the
                # emitted tail now replays through the decode program
                # as forced inputs (this chunk's argmax is a prefill
                # recompute of an already-emitted token — discard it,
                # decode's version is the stream's ground truth). No
                # prefix registration either: the tail blocks would
                # hold decode-built KV, and an adopter's phased twin
                # would prefill them — bit-divergence by adoption.
                slot.replay = req.n_replay
                slot.last_token = int(req.prompt[slot.prefill_pos])
                return
            if self.prefix_cache:
                self.prefix_stats["registered_blocks"] += \
                    self._paged.prefix_register(
                        slot.index, [int(t) for t in req.prompt])
            res = results[req.rid]
            # a resume that had already emitted keeps its stamp
            if res.first_token_s == 0.0:
                res.first_token_s = t1
            res.tokens.append(tok)
            slot_index = slot.index
            reason = sched.record_token(slot, tok)
            if reason is not None:
                res.finish_s, res.finish_reason = t1, reason
                self._free_paged_slot(slot_index)

    def _decode_plan(self, sched: Scheduler, active,
                     admission_blocked: bool = False,
                     prefilling: bool = False) -> int:
        """How many decode steps can run before the host must look.

        Fused runs are only taken when the scheduler can PROVE no
        bookkeeping decision is pending inside the window: no active
        request can hit EOS (host can't predict it), every active
        request has at least ``k`` budget left (length finishes land
        exactly on the window edge), and no admission could happen
        meanwhile (a free slot plus pending work keeps the legacy
        per-token cadence so TTFT never pays for throughput).
        ``admission_blocked`` marks a headroom-deferred queue head: it
        cannot admit until a slot finishes and frees blocks, and
        finishes only land on window edges — so the pending head must
        not hold the whole pool at per-token cadence.
        """
        if self.decode_window <= 1:
            return 1
        if prefilling:
            # a mid-chunked-prefill slot needs its next chunk between
            # every decode step — a fused window would starve its TTFT
            return 1
        if any(s.replay for s in active):
            # replay inputs are FORCED host-side tokens; a fused window
            # chains argmax outputs on device and would feed the wrong
            # token at the second micro-step
            return 1
        if (len(active) < self.n_slots and sched.n_pending
                and not admission_blocked and sched.policy != "fixed"):
            # a free slot could refill mid-window — stay per-token so
            # TTFT never pays for throughput. Under the fixed policy
            # admission waits for ALL slots to drain, so no window can
            # overlap an admission and the drain tail fuses too (both
            # policies run identical programs at identical cadence:
            # speedup_vs_fixed stays a pure scheduling measurement).
            return 1
        if any(s.request.eos_id is not None for s in active):
            return 1
        k = min(s.request.max_new_tokens - s.generated for s in active)
        k = min(k, min(self.max_len - s.pos for s in active))
        return max(1, min(k, self.decode_window))

    def _model_decode_run(self, sched: Scheduler, active, k: int, results,
                          steps, ts, ws, allow_preempt: bool = False):
        """Dispatch ``k`` decode steps with the token stream chained on
        device, then drain all outputs in one batched fetch."""
        span = TraceAnnotation("serve.decode", step=len(steps),
                               iter=self._iter)
        with span:
            nb = used = 0
            if self.cache_kind == "paged":
                if allow_preempt:
                    # chunked mode: growth past the optimistic reservation
                    # evicts younger slots on CacheOOM; grow oldest-first
                    # so eviction flows old -> young, then drop evicted
                    # slots
                    for s in sorted(active,
                                    key=lambda s: (s.request.arrival_s
                                                   if s.request else 0.0,
                                                   s.index)):
                        if not s.decoding:
                            continue   # preempted by an older slot's growth
                        self._ensure_with_preempt(sched, s, s.pos + k,
                                                  results)
                    active = [s for s in active if s.decoding]
                    if not active:
                        return
                else:
                    for s in active:
                        self._paged.ensure(s.index, s.pos + k)
                if self.prefix_cache:
                    # copy-on-write net: decode writes land at pos >=
                    # prompt_len, past every registered (full,
                    # block-aligned) prefix block, so this is structurally
                    # a no-op today — but if a shared block ever ends up
                    # under a write cursor, it is copied out here instead
                    # of corrupting every other reader of that block.
                    srcs: list = []
                    dsts: list = []
                    for s in active:
                        sc, dc = self._paged.make_writable(s.index, s.pos, k)
                        srcs += sc
                        dsts += dc
                    if srcs:
                        self.caches = copy_blocks(
                            self.caches, jnp.asarray(srcs, jnp.int32),
                            jnp.asarray(dsts, jnp.int32))
                tables = self._paged.device_tables()
                nb = self._nb_bucket(self._paged.max_owned())
                step = self._paged_step_fn(nb)
                extra = (tables,)
                if self._rowwise:
                    extra += (jnp.asarray(sched.active_mask()),)
                # micro-step i attends the blocks of pos + i + 1 tokens
                bs = self.block_size
                used = sum(min((s.pos + i) // bs + 1, nb)
                           for s in active for i in range(k))
            else:
                step = self._serve_step
                extra = ()
            span.set_metadata(k=k, nb=nb, rows=self.n_slots, used=used,
                              **self._moe_rows(self.n_slots * k))
            tok = jnp.asarray(sched.input_tokens())
            pos0 = sched.positions()
            adv = sched.active_mask().astype(np.int32)  # idle rows stay parked
            rids = tuple(s.request.rid for s in active)
            t0 = self.clock()
            self._sample_power(ts, ws)   # bracket the decode window
            outs = []
            caches = self.caches
            for i in range(k):
                tok, caches = step(self.params, tok, caches,
                                   jnp.asarray(pos0 + i * adv), *extra)
                try:
                    tok.copy_to_host_async()
                except AttributeError:   # older jax array types
                    pass
                outs.append(tok)
            self.caches = caches
        with TraceAnnotation("serve.decode.fetch"):
            outs_np = [np.asarray(o) for o in outs]  # pipeline drain: 1 sync
            routed = self._fetch_routed()
        t1 = self.clock()
        self._sample_power(ts, ws)
        if self.watchdog is not None:
            self.watchdog.observe(self._decode_idx, (t1 - t0) / k)
        self._decode_idx += 1
        emitted = 0
        with TraceAnnotation("serve.emit", **routed):
            for out in outs_np:
                for s in active:
                    if s.request is None:  # finished at an earlier micro-step
                        continue
                    if s.replay:
                        # preemption-resume replay: this step consumed a
                        # forced emitted-tail token. Mid-replay outputs are
                        # discarded (the stream already has them); the LAST
                        # replay step's argmax is the next NEW token and
                        # falls through to the normal emission path.
                        s.replay -= 1
                        if s.replay:
                            s.pos += 1
                            s.last_token = int(s.request.prompt[s.pos])
                            continue
                    res = results[s.request.rid]
                    tok_i = int(out[s.index])
                    emitted += 1
                    res.tokens.append(tok_i)
                    slot_index = s.index
                    reason = sched.record_token(s, tok_i)
                    if reason is not None:
                        res.finish_s, res.finish_reason = t1, reason
                        if self._paged is not None:
                            self._free_paged_slot(slot_index)
        # rids credit every stepped slot with the window's energy
        # (replay steps burn compute too); n_tokens counts only tokens
        # actually appended to a stream, so token accounting stays exact
        steps.append(StepRecord("decode", t0, t1, rids * k, emitted,
                                n_steps=k, iter=self._iter,
                                rows=self.n_slots, width=nb, used=used))

    # ------------------------------------------------------------------
    # Warmup (compile outside any measured window)
    # ------------------------------------------------------------------

    def warmup(self, prompt_len: int = 8,
               requests: Optional[Sequence[Request]] = None,
               repeat: int = 1, sched: Optional[str] = None):
        """Compile every serve program this engine can reach: the
        prompt-bucket prefill, the insert, and each decode program
        (every paged gather bucket gets crossed as the warmup requests
        grow to full slot capacity). Power sampling and the straggler
        watchdog are detached so warmup never pollutes measurement.

        Pass the measured ``requests`` (and ``repeat=2``) to warm a
        prefix-cached engine: the first pass registers prefixes, the
        second takes the hit path, so every suffix-prefill program
        compiles before measurement. The prefix index is cleared
        afterwards — measured runs start from a cold index either way.
        """
        if self._scripted:
            return
        if requests is None:
            budget = max(self.max_len - prompt_len, 1)
            requests = [Request(rid=-(i + 1),
                                prompt=np.zeros(prompt_len, np.int32),
                                max_new_tokens=budget, arrival_s=0.0)
                        for i in range(self.n_slots)]
        saved = self.power_methods, self.watchdog
        self.power_methods, self.watchdog = [], None
        try:
            for _ in range(max(int(repeat), 1)):
                self.serve(requests, policy="continuous", sched=sched)
        finally:
            self.power_methods, self.watchdog = saved
            self.reset_prefix_cache()

    def reset_prefix_cache(self):
        """Drop every prefix-index entry (freeing index-only blocks) and
        zero the hit counters — each measured run starts cold."""
        if not self._scripted and self._paged is not None \
                and self.prefix_cache:
            self._paged.clear_prefix()
        if not self._scripted:
            self.prefix_stats = self._blank_prefix_stats()

    # ------------------------------------------------------------------
    # Continuous-batching run loop
    # ------------------------------------------------------------------

    def _sample_power(self, ts: list, ws: list):
        if not self.power_methods:
            return
        w = 0.0
        for m in self.power_methods:
            try:
                w += sum(m.read().values())
            except Exception:
                pass  # a failing backend must not kill serving
        ts.append(self.clock())
        ws.append(w)

    def serve(self, requests: Sequence[Request], *,
              policy: str = "continuous",
              poll_s: float = 0.002,
              sched: Optional[str] = None,
              chunk_tokens: Optional[int] = None,
              faults=None) -> ServeRunResult:
        """Run a request set to completion under the given policy.

        Request ``arrival_s`` values are relative to run start; the
        engine sleeps (``sleep_fn``) while the queue is empty and slots
        are idle, so wall time includes genuine arrival gaps.

        ``sched``/``chunk_tokens`` override the engine defaults for
        this run: ``"chunked"`` interleaves block-aligned prefill
        slices with decode steps and backs decode growth with
        preemption (see module docstring) — paged cache, model mode.

        Degradation: requests carrying a ``deadline_s`` are SHED (zero
        tokens, reason "shed") if still queued past their admission
        deadline — the engine never hangs on hopeless work, and shed
        requests count against goodput in ``serve.slo``. ``faults`` is
        an optional seeded :class:`~repro.faults.schedule.FaultSchedule`:
        its overload windows cap the admission queue (shedding newest
        arrivals first — the oldest queued request is never shed), and
        its slot faults kill the youngest decoding slot mid-run
        (chunked mode only: the victim resumes via preemption replay,
        so the faulted stream stays bit-identical to a fault-free one).
        """
        mode = sched or self.sched
        assert mode in ("phased", "chunked"), mode
        ct = int(chunk_tokens if chunk_tokens is not None
                 else self.chunk_tokens)
        chunked = mode == "chunked"
        if chunked:
            assert not self._scripted, (
                "chunked prefill drives the jitted model programs — "
                "scripted engines serve phased only")
            assert self.cache_kind == "paged", (
                "chunked prefill + preemption need block-granular "
                "reclaim (cache='paged')")
            assert ct > 0 and ct % self.block_size == 0, (
                f"chunk_tokens {ct} must be a positive multiple of "
                f"block_size {self.block_size}: chunk boundaries must "
                f"land on block edges so suffix chunks can gather the "
                f"already-prefilled prefix KV block-wise")
        if faults is not None and any(
                e.kind == "slot_fault" for e in faults.events):
            assert chunked and not self._scripted, (
                "slot faults recover via preemption replay, which only "
                "the chunked+paged scheduler implements — phased prefill "
                "cannot rebuild an emitted tail bit-identically")
        if not self._scripted:
            self._ensure_cache()
            self._routed = []
            self.preemptions = 0
        self.shed = 0
        self.injected_faults = 0
        sched = Scheduler(self.n_slots, self.max_len, policy=policy)
        watchdog = self.watchdog
        has_deadlines = any(getattr(r, "deadline_s", None) is not None
                            for r in requests)

        t_start = self.clock()
        results: dict[int, RequestResult] = {}
        for r in requests:
            sched.submit(r)
            results[r.rid] = RequestResult(
                rid=r.rid, prompt_len=r.prompt_len,
                arrival_s=t_start + r.arrival_s,
                tenant=getattr(r, "tenant", ""))
        steps: list[StepRecord] = []
        ts: list[float] = []
        ws: list[float] = []
        if not self._scripted:
            self._defer_free_blocks = None
            self.prefix_stats = self._blank_prefix_stats()
        self._sample_power(ts, ws)

        def _mark_shed(req: Request):
            res = results[req.rid]
            res.finish_s = self.clock()
            res.finish_reason = "shed"
            self.shed += 1

        poll = 0
        while sched.has_work:
            self._iter = poll
            with TraceAnnotation("serve.iter", iter=poll):
                with TraceAnnotation("serve.admit"):
                    now_rel = self.clock() - t_start
                    # -- graceful degradation: deadline expiry + overload
                    if has_deadlines or faults is not None:
                        sched._absorb_arrivals(now_rel)
                        if has_deadlines:
                            for req in sched.shed_expired(now_rel):
                                _mark_shed(req)
                        cap = faults.queue_cap_at(poll) \
                            if faults is not None else None
                        if cap is not None:
                            for req in sched.shed_newest(cap):
                                _mark_shed(req)
                    poll += 1
                    # -- admission: prefill newly admitted requests -------
                    # a headroom-deferred head retries only once
                    # free_blocks has moved — not every loop iteration
                    # (re-admit/unadmit churn)
                    if self._admission_blocked():
                        admitted = []
                    else:
                        if not self._scripted:
                            self._defer_free_blocks = None
                        admitted = sched.refill(now_rel)
                        if admitted and not self._scripted \
                                and self.cache_kind == "paged":
                            admitted = self._admit_paged(
                                sched, admitted, results, chunked=chunked)
                    if chunked and admitted:
                        self._start_chunked(admitted, results)
                if chunked:
                    if any(s.prefilling for s in sched.slots):
                        self._model_prefill_chunks(sched, results, steps,
                                                   ts, ws, ct)
                elif admitted and not self._scripted:
                    self._model_prefill_admitted(sched, admitted, results,
                                                 steps, ts, ws)
                elif admitted:
                    for slot in admitted:
                        req = slot.request
                        res = results[req.rid]
                        res.slot = slot.index
                        res.admitted_s = self.clock()
                        self._sample_power(ts, ws)   # bracket the window
                        first = self._slot_prefill(slot.index, req.prompt)
                        t1 = self.clock()
                        self._sample_power(ts, ws)
                        res.first_token_s = t1
                        res.tokens.append(int(first))
                        steps.append(StepRecord("prefill", res.admitted_s,
                                                t1, (req.rid,), 1,
                                                iter=self._iter))
                        reason = sched.record_token(slot, int(first))
                        if reason is not None:
                            res.finish_s, res.finish_reason = t1, reason
                # -- decode over all fully-prefilled slots ----------------
                active = sched.decode_slots()
                if (faults is not None and active
                        and faults.slot_fault_at(self._decode_idx)):
                    # injected slot failure: evict the YOUNGEST decoding
                    # slot (never the oldest — FIFO degradation). The
                    # victim re-queues at the front and resumes via decode
                    # replay, so its stream stays bit-identical to a
                    # fault-free run.
                    victim = max(active, key=lambda s: (s.request.arrival_s,
                                                        s.request.rid))
                    self._preempt_slot(sched, victim, results)
                    self.injected_faults += 1
                    active = sched.decode_slots()
                prefilling = any(s.prefilling for s in sched.slots)
                if active and not self._scripted:
                    k = self._decode_plan(
                        sched, active,
                        admission_blocked=self._admission_blocked(),
                        prefilling=prefilling)
                    self._model_decode_run(sched, active, k, results,
                                           steps, ts, ws,
                                           allow_preempt=chunked)
                elif active:
                    rids = tuple(s.request.rid for s in active)
                    t0 = self.clock()
                    self._sample_power(ts, ws)   # bracket the decode window
                    out = self._slot_decode(sched.input_tokens(),
                                            sched.positions(),
                                            sched.active_mask())
                    t1 = self.clock()
                    self._sample_power(ts, ws)
                    if watchdog is not None:
                        watchdog.observe(self._decode_idx, t1 - t0)
                    self._decode_idx += 1
                    steps.append(StepRecord("decode", t0, t1, rids,
                                            len(rids), iter=self._iter))
                    out = np.asarray(out)
                    for s in active:
                        res = results[s.request.rid]
                        tok = int(out[s.index])
                        res.tokens.append(tok)
                        reason = sched.record_token(s, tok)
                        if reason is not None:
                            res.finish_s, res.finish_reason = t1, reason
                elif sched.n_pending and not prefilling:
                    # idle: nothing admitted yet — wait for the next arrival
                    nxt = sched.next_arrival_s()
                    wait = (t_start + nxt) - self.clock() \
                        if nxt is not None else poll_s
                    if wait > 0:
                        with TraceAnnotation("serve.wait"):
                            self.sleep_fn(min(wait, 0.05))

        self._sample_power(ts, ws)
        out_results = sorted(results.values(), key=lambda r: r.finish_s)
        for rid, wh in attribute_energy(steps, ts, ws).items():
            results[rid].energy_wh = wh
        return ServeRunResult(
            results=out_results, steps=steps, sample_ts=ts, sample_ws=ws,
            summary=serve_summary(out_results, steps, ts, ws,
                                  n_slots=self.n_slots),
            straggler_events=list(watchdog.events) if watchdog else [])

    # ------------------------------------------------------------------
    # Fixed-batch generation (legacy BatchedServer path)
    # ------------------------------------------------------------------

    def generate(self, tokens: jax.Array, n_steps: int,
                 extras: Optional[dict] = None,
                 gen_budget: Optional[int] = None) -> GenerationResult:
        """Fixed-batch greedy decode: prefill a full batch, decode
        ``n_steps`` with a shared scalar position. ``gen_budget`` sets
        the KV growth beyond the prompt (defaults to n_steps + 1)."""
        assert not self._scripted
        extras = extras or {}
        budget = gen_budget if gen_budget is not None else n_steps + 1
        b, s = tokens.shape
        t0 = time.perf_counter()
        logits, caches, enc_kv = self._prefill(self.params, tokens, extras)
        logits.block_until_ready()
        t1 = time.perf_counter()
        # grow KV caches so decode can append (SSM states pass through)
        caches = self._grow(caches, s + budget)
        out = [jnp.argmax(logits[:, -1], -1).astype(jnp.int32)]
        pos = s
        for _ in range(n_steps - 1):
            tok = out[-1][:, None]
            logits, caches = self._decode(self.params, tok, caches,
                                          jnp.int32(pos), enc_kv)
            out.append(jnp.argmax(logits[:, -1], -1).astype(jnp.int32))
            pos += 1
        out[-1].block_until_ready()
        t2 = time.perf_counter()
        return GenerationResult(jnp.stack(out, 1), n_steps, t1 - t0, t2 - t1)


class BatchedServer:
    """Fixed-batch greedy decoding driver — one policy over ServeEngine.

    Back-compat shim: ``max_len`` keeps its historical meaning here (KV
    growth budget beyond the prompt), while ``ServeEngine.max_len`` is
    the total slot capacity.
    """

    def __init__(self, c: ModelConfig, params: Params, *,
                 max_len: int = 256, impl_prefill: str = "repeat",
                 impl_decode: str = "grouped", donate: bool = True):
        self.c, self.params, self.max_len = c, params, max_len
        self.engine = ServeEngine(
            c, params, n_slots=1, max_len=max_len,
            impl_prefill=impl_prefill, impl_decode=impl_decode,
            donate=donate)

    def generate(self, tokens: jax.Array, n_steps: int,
                 extras: Optional[dict] = None) -> GenerationResult:
        return self.engine.generate(tokens, n_steps, extras,
                                    gen_budget=self.max_len)
