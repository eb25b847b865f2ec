"""Continuous-batching serving engine with full or KQ-SVD-compressed cache.

True continuous batching over fixed cache slots (DESIGN.md §decode),
scheduled as explicit ``step()`` iterations (sarathi-style):

* the batched cache is allocated once; ``step()`` admits pending
  requests into free slots, advances in-flight chunked prefills, runs
  one fused decode chunk and harvests finished slots — prefill and
  decode work interleave instead of prefill stalling the whole batch;
* decode runs as a fused ``lax.scan`` of ``decode_chunk`` steps entirely
  on device: sampling, EOS / ``max_new_tokens`` / capacity masking and
  per-slot position increments all live inside the scan, so the host
  syncs once per chunk instead of once per token;
* slots whose request finished are refilled from the pending queue at
  the next chunk boundary while the other slots keep decoding.

Two cache layouts (``ServeConfig.paged``):

* **dense** (default, the parity reference): every slot owns a
  ``max_seq_len`` lane, so HBM scales with the worst-case request;
* **paged** (DESIGN.md §paged-cache): each layer's cache is a pool of
  fixed-size pages shared by all slots through a block table.
  Admission allocates ``ceil(prompt/page_size)`` pages on demand (with
  backpressure when the pool is short), ``decode_chunk`` headroom is
  allocated at each chunk boundary so sequences grow page-by-page, and
  finished slots return their pages to the pool without draining the
  batch — HBM scales with *occupied pages*, not
  ``max_batch * max_seq_len``.

Two paged admission policies (``ServeConfig.admission``, DESIGN.md
§preemption):

* **reserve** (default, the parity oracle): admission reserves the
  request's *worst-case* ``ceil(min(prompt+max_new, T)/page_size)``
  pages, so decode growth can never strand a live sequence — at the
  cost of sizing the pool for a worst case that rarely materializes;
* **optimistic**: admission charges only the prompt footprint (capped
  by the pool's high watermark) and oversubscribes the rest.  When
  ``decode_chunk`` headroom would exhaust the pool, LIFO victims are
  preempted: their pages are released (freeing ``watermark_low`` extra
  slack as a thrash guard) and they are requeued at the head of the
  pending queue — either carrying their generated tokens as prompt
  suffix so prefill *recomputes* the cheap compressed cache
  (``preempt_mode="recompute"``), or round-tripping their pages
  through a host-RAM buffer (``preempt_mode="swap"``).  Under no
  pressure the two policies are token-for-token identical.

In either policy a request whose worst case exceeds the *whole* pool
can never complete, even alone: it is marked ``failed`` at admission
and the rest of the batch keeps serving (no mid-serve raise), and
``_admit`` scans a bounded ``admit_window`` of the pending queue so a
small request is not head-of-line blocked behind a big one.

Two prefill paths (``ServeConfig.chunked_prefill``, DESIGN.md §prefill):

* **exact-length** (default, the parity oracle): each request prefills
  alone at its exact prompt length — one XLA compile per distinct
  length — and (paged) stages the cache through a dense
  ``(1, max_seq_len)`` buffer before repaging;
* **chunked** (requires paged): prompts split into
  ``prefill_chunk``-sized chunks padded to a small set of bucket
  lengths (at most ``len(buckets)`` prefill compiles per engine
  lifetime) that write the compressed ``R_k/R_v`` entries straight
  into pages — no staging buffer — and are scheduled a few chunks per
  ``step()`` so other slots keep decoding while a long prompt
  prefills.  Partially-prefilled slots hold their pages and join
  decode only when complete; their block-table rows export as the
  garbage page to the decode scan, so its masked writes cannot touch
  pages the prefill is filling.

Cross-request prefix sharing (``ServeConfig.share_prefix``, DESIGN.md
§prefix-sharing, requires chunked+paged): pages are refcounted and a
host-side prefix index maps chained hashes of page-aligned token
chunks to the physical pages already holding their (compressed) cache
entries.  Admission maps the longest cached prefix into the new slot's
block table by reference — charging only the *unshared* tail against
the pool — and chunked prefill starts past it (an exact-duplicate
prompt with stored terminal logits skips prefill entirely).  Writes
into a still-shared page copy-on-write fork it first, so two requests
sharing a prefix can diverge mid-decode without corrupting each other;
a finished request's pages stay pinned by the index for reuse until
reclaimed under pool pressure.  With sharing off (the default) the
engine is byte-identical to the PR 4 behavior and stays the parity
oracle.

Failure semantics (DESIGN.md §robustness): every way a request can
fail is a *structured, per-request* outcome, never a mid-serve abort —
``Request.error`` carries a ``RequestError`` with a ``kind`` from the
taxonomy (``oversize | deadline | pool_exhausted | swap_failed |
numerics | cancelled``) and the rest of the batch keeps serving.
Per-request deadlines (``deadline_steps`` / ``ttft_deadline_steps``,
in engine steps), a public ``cancel(rid)`` that unwinds a request at
any lifecycle stage, bounded retry-with-backoff for transient
admission failures, NaN/inf logit quarantine of single slots, and
swap-in failure degrading to recompute all route through the same
``_fail_request`` unwind.  A seedable ``FaultInjector``
(``serving/faults.py``) can force each of those rare paths
deterministically, and ``invariants.audit`` (``ServeConfig.audit``)
cross-checks refcounts / free list / block tables after every step.
A ``stall_steps`` no-progress watchdog turns scheduler livelock into
``EngineStalledError`` with a state dump instead of a silent spin.

Token-budget scheduling (``ServeConfig.max_num_batched_tokens``,
DESIGN.md §scheduler): with a positive budget every ``step()`` spends
one global token budget instead of the per-request admit loop — each
decoding slot charges 1 token first, admission stops once occupancy
reaches the budget, and prefill chunks fill the residual (the last
chunk truncated to it, sarathi-style).  One staged chunk fuses into
the decode scan's dispatch (``_fused_step``), so the common steady
state is a *single* device call per step and per-step cost is bounded
by the budget whatever the prefill:decode mix.  Greedy outputs are
scheduling-invariant, so the legacy path (budget 0, the default)
stays the token-for-token parity oracle; the chaos / audit layers run
unchanged on either scheduler.

Every sequence carries its own position: the decode stack (and on TPU
the Pallas kernel) masks per-sequence lengths, so a mixed-length batch
pays for the cache it occupies, not for ``max_seq_len``.  With KQ-SVD
compression the same HBM budget admits ~d/(R_k+R_v) x more concurrent
sequences (``capacity_gain``) — the serving-level payoff of the paper.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, ServeConfig
from repro.core.calibration import ModelProjections
from repro.core.compressed import cache_footprint
from repro.kernels.kq_decode import default_decode_splits
from repro.serving import invariants
from repro.serving.faults import FaultInjector, SwapFailed, checksum
from repro.serving.page_layouts import FpLayout, get_layout
from repro.serving.paged_cache import (GARBAGE_PAGE, BlockTables, PagePool,
                                       PagePoolExhausted, PrefixIndex,
                                       copy_page, pages_needed, swap_in,
                                       swap_out)
from repro.sharding import partition
from repro.models.model import build_model

# the structured failure taxonomy (DESIGN.md §robustness): every
# terminal non-success outcome of a request is exactly one of these
ERROR_KINDS = ("oversize", "deadline", "pool_exhausted", "swap_failed",
               "numerics", "cancelled")


@dataclasses.dataclass
class RequestError:
    """Why a request terminally failed (``Request.error``).

    kind: one of ``ERROR_KINDS`` —
      * ``oversize``: worst-case page footprint exceeds the whole pool
        (could never complete, even alone);
      * ``deadline``: ``ttft_deadline_steps`` / ``deadline_steps``
        budget exhausted before the first / last token;
      * ``pool_exhausted``: transient admission allocation failed more
        than ``ServeConfig.admission_retries`` times (backoff spent);
      * ``swap_failed``: a swapped-out cache could not be restored and
        recompute fallback is disabled (``swap_fallback=False``);
      * ``numerics``: non-finite next-token logits — the slot was
        quarantined so the rest of the batch keeps decoding;
      * ``cancelled``: ``engine.cancel(rid)``.
    """
    kind: str
    detail: str = ""
    step: int = -1                     # engine step of the failure

    def __post_init__(self) -> None:
        if self.kind not in ERROR_KINDS:
            raise ValueError(f"unknown error kind {self.kind!r} "
                             f"(known: {ERROR_KINDS})")


class EngineStalledError(RuntimeError):
    """``step()`` made no scheduling progress for ``stall_steps``
    consecutive iterations (e.g. preemption livelock under a tiny
    pool).  Carries a scheduler-state dump instead of spinning
    ``generate()`` forever."""

    def __init__(self, n_steps: int, dump: str):
        self.n_steps = n_steps
        self.dump = dump
        super().__init__(
            f"engine made no scheduling progress for {n_steps} "
            f"consecutive steps (no new tokens, no prefill advance, "
            f"no completions)\n{dump}")


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request, mutated in place as it is served.

    Inputs: ``rid`` (caller's id), ``prompt``, ``max_new_tokens``,
    optional ``priority`` tier and per-request deadlines.  Outputs:
    ``out_tokens`` accumulates generated ids; exactly one terminal
    outcome holds afterwards — ``done`` (optionally ``truncated``) or
    ``failed`` with ``error`` carrying the structured cause.  The
    lifecycle state machine is documented in docs/SERVING.md."""
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    priority: int = 0                  # SLA tier: preemption evicts lower
                                       # priority first (ties: LIFO stamp)
    # deadlines in engine steps since start() (None = unbounded):
    # ttft bounds the wait for the *first* token, deadline_steps the
    # whole request; exceeding either fails the request with
    # error.kind == "deadline" and unwinds it (DESIGN.md §robustness)
    deadline_steps: Optional[int] = None
    ttft_deadline_steps: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False            # hit max_seq_len before max_new_tokens
    error: Optional[RequestError] = None   # structured terminal failure
    # host-clock stamps (time.perf_counter()): handed to start(), first
    # admitted to a slot, first token on the host — queue wait is
    # t_admitted - t_submitted
    t_submitted: Optional[float] = None
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None

    @property
    def failed(self) -> bool:
        """Terminal failure of any kind (``error`` holds the cause)."""
        return self.error is not None


def sample_token(logits: jnp.ndarray, temperature: float, rng) -> jnp.ndarray:
    """Sample next-token ids from ``(B, V)`` logits.

    Greedy argmax at ``temperature <= 0`` (the deterministic parity
    mode every scheduling-invariance test relies on); otherwise a
    temperature-scaled categorical draw from ``rng``."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, logits / temperature, axis=-1)


# -- instrumentation ---------------------------------------------------------
# Host spans (``TraceAnnotation``) name the engine's host work in a
# profiler trace, on the device trace's clock; without a profiler attached
# each costs about a microsecond.  docs/SERVING.md §Tracing lists them.


def _to_host(x, what: str, copy: bool = False) -> np.ndarray:
    """``np.asarray(x)`` (``np.array`` with ``copy``).  Every blocking
    device-to-host read of the step path goes through here, under an
    ``engine.fetch`` span whose ``what`` names the value read; a host
    array passes through without a span."""
    read = np.array if copy else np.asarray
    if not isinstance(x, jax.Array):
        return read(x)
    with TraceAnnotation("engine.fetch", what=what):
        return read(x)


def _stamp_submitted(requests: List[Request]) -> None:
    """``start`` has made room for ``requests``: submitted now, not yet
    admitted."""
    now = time.perf_counter()
    for r in requests:
        r.t_submitted, r.t_admitted, r.t_first_token = now, None, None


class ServingEngine:
    """Continuous-batching serving engine (see the module docstring
    for the full design).

    Public surface: ``start(requests)`` allocates serving state,
    ``step()`` advances one scheduling iteration, ``generate`` is the
    start-and-drain loop, ``cancel(rid)`` unwinds one request at any
    lifecycle stage.  Requests mutate in place — ``out_tokens``
    accumulates, ``done``/``truncated``/``error`` report the terminal
    outcome.  Counters (``n_preempted``, ``n_failed``,
    ``error_counts``, ``budget_log``, ...) expose scheduler behavior
    to tests, benches and the CLI; docs/SERVING.md is the operator
    guide."""

    def __new__(cls, cfg=None, params=None, sc=None, *args, **kwargs):
        """Route construction to the data-sharded engine when the
        config asks for more than one shard (DESIGN.md
        §sharded-engine).  ``shards == 1`` — and any explicit subclass
        construction — takes the ordinary path, so the single-device
        engine stays the bitwise parity oracle."""
        sc = kwargs.get("sc", sc)
        if cls is ServingEngine and sc is not None and sc.shards > 1:
            return super().__new__(ShardedServingEngine)
        return super().__new__(cls)

    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig,
                 projections: Optional[ModelProjections] = None,
                 faults: Optional[FaultInjector] = None):
        # the serve config owns the paged page layout (DESIGN.md
        # §page-layouts): fold it into the model config before
        # build_model so every attention path — prefill staging,
        # chunked prefill, decode — resolves the same layout.
        # Quantized layouts compress the projected R_k/R_v page
        # entries (the paper's setting); a full-cache engine (no
        # projections) has none, so it keeps serving fp pages and the
        # request is recorded inert rather than rejected.
        if sc.cache_quant != "none" and projections is not None:
            cfg = dataclasses.replace(cfg, cache_quant=sc.cache_quant)
        self.cfg = cfg
        self.sc = sc
        # explicit injector (tests / chaos drivers) wins over the
        # config-built chaos schedule; None = no injection
        self._faults_arg = faults
        self.faults: Optional[FaultInjector] = None
        self.model = build_model(cfg)
        self.params = params
        self.proj = (self.model.projections_pytree(projections)
                     if projections is not None else None)
        self.ranks = ((projections.rank_k, projections.rank_v)
                      if projections is not None else (0, 0))
        # physical-page capacity multiplier of the active page layout
        # (DESIGN.md §page-layouts): quantized pages are narrower than
        # fp pages, so the same HBM byte budget (``ServeConfig.n_pages``
        # counts fp-sized pages) holds ``capacity_x`` more physical
        # pages.  Admission watermarks, worst-case reservation and the
        # pool itself are all sized from the physical count — fp
        # layouts keep capacity_x == 1.0 and stay bitwise unchanged.
        self.capacity_x = self._capacity_multiplier()
        if sc.paged:
            self._validate_paged()
        # split-KV flash-decoding fan-out (DESIGN.md §split-kv): a
        # fixed positive count is resolved once at construction; 0
        # re-derives the count per step from the live maximum sequence
        # length, snapped down to {1, 2, 4, 8} so the decode dispatch
        # compiles at most four split variants
        self._decode_splits = 1
        self._dynamic_splits = False
        if sc.paged:
            if sc.decode_splits:
                self._decode_splits = sc.decode_splits
            else:
                self._dynamic_splits = True
        self._prefill = jax.jit(self._prefill_impl)
        self._insert = jax.jit(self._insert_impl)
        self._paged_insert = jax.jit(self._paged_insert_impl)
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl)
        self._decode_chunk = jax.jit(self._decode_chunk_impl,
                                     static_argnames=("num_splits",))
        self._fused_step = jax.jit(self._fused_step_impl,
                                   static_argnames=("num_splits",))
        self._fork_page = jax.jit(self._fork_page_impl)
        self.rng = jax.random.PRNGKey(sc.seed)
        # distinct chunk shapes traced so far — the compile-count bound
        # is len(sc.buckets) per engine lifetime (tests assert on it)
        self.prefill_chunk_shapes: set = set()
        self._started = False

    def _capacity_multiplier(self) -> float:
        """Physical pages per fp-page of HBM under the active layout.

        The ratio of fp token bytes to the layout's token bytes at the
        engine's ranks (page_layouts ``token_bytes``); 1.0 for fp pages
        or when serving without projections (no quantized layout)."""
        if not self.sc.paged or self.ranks[0] == 0 \
                or self.sc.cache_quant == "none":
            return 1.0
        layout = get_layout(self.cfg)
        rk, rv = self.ranks
        fp = FpLayout()
        fp_bytes = fp.token_bytes("k", rk) + fp.token_bytes("v", rv)
        q_bytes = layout.token_bytes("k", rk) + layout.token_bytes("v", rv)
        return fp_bytes / q_bytes

    def _pool_pages(self) -> int:
        """Allocatable physical page count: the configured fp-unit HBM
        budget (``ServeConfig.total_pages``) scaled by the layout's
        capacity multiplier.  Watermarks (pool fractions) and the
        oversize/worst-case admission checks all derive from this, so
        quantized pools no longer under-admit in fp-page units."""
        return max(1, int(self.sc.total_pages * self.capacity_x))

    def _validate_paged(self) -> None:
        """Fail fast at construction, not mid-serve."""
        cfg = self.cfg
        kinds = set(cfg.layer_kinds())
        if kinds != {"attn"}:
            raise NotImplementedError(
                f"paged serving supports plain attention stacks only "
                f"(layer kinds: {sorted(kinds)})")
        if cfg.sliding_window:
            raise NotImplementedError(
                "paged serving: sliding window not supported")
        if cfg.cache_quant != "none" and self.sc.cache_quant == "none":
            raise NotImplementedError(
                "paged serving selects its page layout via "
                "ServeConfig.cache_quant (DESIGN.md §page-layouts); "
                "ModelConfig.cache_quant alone configures the *dense* "
                "int8 cache only")

    def _splits_for_step(self, live_max: int) -> int:
        """Static split count for one decode dispatch.

        Fixed ``decode_splits`` passes through; dynamic mode
        (``decode_splits == 0``) feeds the *live* maximum sequence
        length — the tokens this chunk can actually touch, not the
        ``max_seq_len`` worst case — through the split heuristic and
        snaps the result down to {1, 2, 4, 8}, bounding the dispatch
        at four compiled variants per engine lifetime."""
        if not self._dynamic_splits:
            return self._decode_splits
        raw = default_decode_splits(
            max(1, min(live_max, self.sc.max_seq_len)), self.sc.page_size)
        for snapped in (8, 4, 2):
            if raw >= snapped:
                return snapped
        return 1

    def _live_splits(self, live: np.ndarray) -> int:
        """Split count for the chunk about to dispatch: the live slots'
        deepest position plus the chunk's growth is the most cache the
        scan can touch."""
        if not self.sc.paged or not self._dynamic_splits:
            return self._decode_splits
        pos_np = _to_host(self._pos, "pos")
        live_max = int(pos_np[live].max()) if live.any() else 1
        return self._splits_for_step(live_max + self.sc.decode_chunk)

    # -- jitted internals ---------------------------------------------------

    def _prefill_impl(self, params, proj, tokens):
        """One request at its exact prompt length -> (logits, slot cache)."""
        batch = {"tokens": tokens}
        if self.proj is not None:
            return self.model.prefill(params, batch, self.sc.max_seq_len,
                                      proj=proj)
        return self.model.prefill(params, batch, self.sc.max_seq_len)

    def _prefill_chunk_impl(self, params, proj, cache, tokens, pos0,
                            n_valid, btab_row):
        """One bucket-padded prompt chunk -> (last-valid logits, cache).

        tokens: (1, bucket) chunk, first ``n_valid`` entries real;
        pos0: (1,) tokens already written for this sequence.  Writes
        the chunk's entries straight into the page pools through
        ``btab_row`` and returns the logits of the last *valid* token
        (the next-token carry once the final chunk lands).  ``n_valid``
        flows down as a per-row count (the budget-truncated
        ``append_chunk`` form, DESIGN.md §scheduler) — the model layer
        derives the prefix mask where it needs one.  Compiles once per
        bucket shape."""
        valid = n_valid
        kw: Dict[str, Any] = {"block_table": btab_row}
        if self.proj is not None:
            kw["proj"] = proj
        with jax.named_scope("prefill_chunk"):
            logits, cache = self.model.prefill_chunk(params, cache, tokens,
                                                     pos0, valid, **kw)
            last = jnp.take_along_axis(
                logits, (n_valid - 1)[:, None, None], axis=1)[:, 0]
        return last, cache

    def _insert_impl(self, cache, slot_cache, slot):
        """Write a single-sequence cache into batch slot ``slot``."""
        def _at_batch0(big, small):
            return jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), slot, 0)

        def _at_batch1(big, small):          # scanned steps: (n_steps, B, ...)
            return jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), slot, 1)

        out = {"prefix": jax.tree.map(_at_batch0, cache["prefix"],
                                      slot_cache["prefix"])}
        out["steps"] = (jax.tree.map(_at_batch1, cache["steps"],
                                     slot_cache["steps"])
                        if cache["steps"] is not None else None)
        return out

    def _paged_insert_impl(self, cache, slot_cache, phys):
        """Scatter a prefilled slot cache into the page pools.

        ``slot_cache`` leaves are dense (1, Hkv, T, R) (the exact-length
        prefill contract is unchanged); they are cut into
        (T / page_size) pages and the first ``len(phys)`` — the pages
        the prompt occupies — are written at the allocated physical
        ids.  Int8-layout staging additionally carries (1, Hkv, T)
        scale leaves (the dense int8 prefill contract), repaged into
        the (P, Hkv, ps, 1) scale pools in lockstep with their data
        pages.  Compiles once per distinct page count, same as prefill
        per distinct length.  The chunked path writes pages directly
        and never builds this staging buffer."""
        ps = self.sc.page_size
        n = phys.shape[0]

        def _repage0(pool, dense):           # dense (1, Hkv, T[, R])
            if dense.ndim == 3:              # scale leaf: (1, Hkv, T)
                hkv, t = dense.shape[1:]
                pages = dense[0].reshape(hkv, t // ps, ps).transpose(
                    1, 0, 2)[..., None]
                return pool.at[phys].set(pages[:n].astype(pool.dtype))
            hkv, t, r = dense.shape[1:]
            pages = dense[0].reshape(hkv, t // ps, ps, r).transpose(
                1, 0, 2, 3)
            return pool.at[phys].set(pages[:n].astype(pool.dtype))

        def _repage1(pool, dense):           # (n_steps, 1, Hkv, T[, R])
            if dense.ndim == 4:              # scale leaf
                nl, _, hkv, t = dense.shape
                pages = dense[:, 0].reshape(nl, hkv, t // ps, ps).transpose(
                    0, 2, 1, 3)[..., None]
                return pool.at[:, phys].set(pages[:, :n].astype(pool.dtype))
            nl, _, hkv, t, r = dense.shape
            pages = dense[:, 0].reshape(nl, hkv, t // ps, ps, r).transpose(
                0, 2, 1, 3, 4)
            return pool.at[:, phys].set(pages[:, :n].astype(pool.dtype))

        out = {"prefix": jax.tree.map(_repage0, cache["prefix"],
                                      slot_cache["prefix"])}
        out["steps"] = (jax.tree.map(_repage1, cache["steps"],
                                     slot_cache["steps"])
                        if cache["steps"] is not None else None)
        return out

    def _fork_page_impl(self, cache, src, dst):
        """Copy physical page ``src`` to ``dst`` in every layer's pools
        (the device half of a copy-on-write fork; the host half
        repoints the writer's block-table row at ``dst``).  Scalar
        src/dst, so this compiles once."""
        def _c0(pool):                       # prefix leaves: (P, ...)
            return copy_page(pool, src, dst)

        def _c1(pools):                      # scanned steps: (n_steps, P, ...)
            return pools.at[:, dst].set(pools[:, src])

        out = {"prefix": jax.tree.map(_c0, cache["prefix"])}
        out["steps"] = (jax.tree.map(_c1, cache["steps"])
                        if cache["steps"] is not None else None)
        return out

    def _decode_chunk_impl(self, params, proj, cache, logits, pos, emitted,
                           max_new, done, trunc, rng, block_table,
                           num_splits=1):
        """Fused ``decode_chunk``-step decode, fully on device.

        logits: (B, V) next-token logits per slot; pos: (B,) index where
        each slot's next token will be written (== live length); the
        sampled-token / emit-mask streams come back (N, B).
        ``block_table`` is None for the dense cache.  ``num_splits``
        (static) selects split-KV flash-decoding in the paged path —
        ``_splits_for_step`` resolves it per dispatch."""
        T = self.sc.max_seq_len
        temp = self.sc.temperature
        eos = self.sc.eos_token

        def _decode(cache, tokens, fpos, live):
            kw: Dict[str, Any] = {"block_table": block_table,
                                  "token_mask": live}
            if self.proj is not None:
                kw["proj"] = proj
            if block_table is not None:
                kw["num_splits"] = num_splits
            return self.model.decode_step(params, cache, tokens, fpos,
                                          **kw)

        def _body(carry, _):
            logits, cache, pos, emitted, done, trunc, rng = carry
            rng, sub = jax.random.split(rng)
            nxt = sample_token(logits, temp, sub).astype(jnp.int32)  # (B,)
            emit = ~done
            out_tok = jnp.where(emit, nxt, 0)
            emitted = emitted + emit.astype(jnp.int32)
            done = done | (emitted >= max_new)
            if eos is not None:
                done = done | (emit & (nxt == eos))
            # the sampled token was emitted but there is no cache slot
            # left to decode from it: surface truncation, stop the slot
            full = ~done & (pos >= T)
            trunc = trunc | full
            done = done | full
            active = ~done
            feed_pos = jnp.minimum(pos, T - 1)  # done slots: harmless write
            # (paged: a freed or mid-prefill slot's block-table row
            # points at the garbage page, so the masked write cannot
            # touch pages that were recycled to other sequences or that
            # a concurrent chunked prefill is filling)

            def _step(ops):
                lg, new_cache = _decode(ops[0], ops[1][:, None], ops[2],
                                       ops[3])
                return lg[:, 0], new_cache

            def _skip(ops):
                return logits, ops[0]

            new_logits, cache = jax.lax.cond(
                jnp.any(active), _step, _skip, (cache, nxt, feed_pos,
                                              active))
            pos = jnp.where(active, pos + 1, pos)
            return ((new_logits, cache, pos, emitted, done, trunc, rng),
                    (out_tok, emit))

        carry = (logits, cache, pos, emitted, done, trunc, rng)
        with jax.named_scope("decode_chunk"):
            carry, (toks, emits) = jax.lax.scan(
                _body, carry, None, length=self.sc.decode_chunk)
        return carry, toks, emits

    def _fused_step_impl(self, params, proj, cache, pf_tokens, pf_pos0,
                         pf_n_valid, pf_row, logits, pos, emitted,
                         max_new, done, trunc, rng, block_table,
                         num_splits=1):
        """One fused scheduling iteration: a prefill chunk piggybacks
        on the decode scan in a single device dispatch (sarathi-style,
        DESIGN.md §scheduler).

        The chunk's pages are written first, then the decode scan runs
        against the updated pools — safe in either order, because a
        mid-prefill slot's block-table row exports as the garbage page
        to the scan, so its masked writes cannot touch the pages the
        chunk is filling.  Compiles once per prefill bucket shape (the
        decode half is shape-stable), so the compile bound stays
        ``len(buckets)`` for this path.  Returns
        ``(chunk last-valid logits, decode carry, tokens, emit mask)``.
        """
        with jax.named_scope("fused_step"):
            last, cache = self._prefill_chunk_impl(
                params, proj, cache, pf_tokens, pf_pos0, pf_n_valid, pf_row)
            carry, toks, emits = self._decode_chunk_impl(
                params, proj, cache, logits, pos, emitted, max_new, done,
                trunc, rng, block_table, num_splits)
        return last, carry, toks, emits

    # -- capacity accounting --------------------------------------------------

    def capacity_gain(self) -> float:
        """How many x more sequences fit in the same cache HBM."""
        if self.ranks[0] == 0:
            return 1.0
        fp = cache_footprint(self.cfg.n_kv_heads, self.cfg.d_head,
                             *self.ranks)
        return 1.0 / fp.ratio

    # -- serving ------------------------------------------------------------

    def start(self, requests: List[Request]) -> None:
        """Initialize serving state for a batch of requests.

        Allocates the (dense or paged) cache and the per-slot decode
        state; ``step()`` then advances admission / prefill / decode one
        scheduling iteration at a time (``generate`` is the drain
        loop)."""
        sc = self.sc
        B, T = sc.max_batch, sc.max_seq_len
        # validate before any work: a mid-serve raise would abandon
        # already-admitted in-flight requests.  The budget scheduler
        # instead fails oversize prompts per-request at admission
        # (error.kind == "oversize") — one batch member can never
        # abort the rest (DESIGN.md §scheduler).
        if not sc.max_num_batched_tokens:
            for r in requests:
                if len(r.prompt) > T:
                    raise ValueError(
                        f"request {r.rid}: prompt length {len(r.prompt)}"
                        f" exceeds max_seq_len {T}")
        self._pending: List[Request] = list(requests)
        self._all_requests: List[Request] = list(requests)
        # fault injection (DESIGN.md §robustness): an injector passed
        # to the constructor is reused across drains (tests own its
        # schedule); the config-built chaos schedule is rebuilt per
        # start() so every drain reproduces bit-for-bit from
        # (chaos_seed, chaos_rate)
        if self._faults_arg is not None:
            self.faults = self._faults_arg
        elif sc.chaos_seed is not None:
            self.faults = FaultInjector.chaos(sc.chaos_seed,
                                              sc.chaos_rate)
        else:
            self.faults = None
        self._reserved = [0] * B   # worst-case *logical* pages per slot
        #                            (growth cap on the block-table row)
        self._charged = [0] * B    # worst-case pages the slot may newly
        #                            allocate: private tail only — shared
        #                            prefix pages are charged to nobody
        #                            (they exist once, whoever shares them)
        self._private = [0] * B    # pages currently allocated (not shared)
        self.pool = None           # introspection (tests/bench)
        self._btabs = None
        self._pindex = None
        if sc.paged:
            # pool and cache are sized in *physical* pages: the fp-unit
            # HBM budget times the layout's capacity multiplier, so
            # watermarks and worst-case reservation stop under-admitting
            # quantized pools (satellite of DESIGN.md §page-layouts)
            n_phys = self._pool_pages()
            self.pool = PagePool(n_phys, sc.watermark_high,
                                 sc.watermark_low)
            self.pool.faults = self.faults
            self._btabs = BlockTables(B, sc.pages_per_seq)
            self._cache = self.model.init_paged_cache(
                n_phys + 1, sc.page_size, self.ranks)
            if sc.share_prefix:
                # per-batch prefix index (DESIGN.md §prefix-sharing):
                # reset with the pool, since its entries pin pool pages
                self._pindex = PrefixIndex(sc.prefix_index_capacity)
        else:
            self._cache = self.model.init_cache(B, T, self.ranks)
        # preemption bookkeeping (DESIGN.md §preemption)
        self._stamp = [0] * B      # admission order per slot (LIFO victims)
        self._admit_seq = 0
        self._swapped: Dict[int, Dict[str, Any]] = {}   # id(req) -> state
        self.n_preempted = 0
        self.n_swapped_out = 0
        self.n_swapped_in = 0
        self.n_failed = 0
        self.preempted_rids: List[int] = []
        # robustness bookkeeping (DESIGN.md §robustness)
        self._step_count = 0
        self._no_progress = 0      # consecutive no-progress steps
        self._progress = False     # set by prefill advance / emits /
        #                            completions within the current step
        self._retry: Dict[int, tuple] = {}   # id(req) -> (n, retry_at)
        self._pf_best: Dict[int, int] = {}   # id(req) -> prefill high-
        #                                      watermark (absolute pos):
        #                                      re-prefill after preemption
        #                                      is thrash, not progress
        self.n_completed = 0
        self.n_audits = 0          # invariants.audit passes actually run
        self.n_retried = 0         # admission alloc retries (backoff)
        self.n_swap_fallbacks = 0  # swap faults degraded to recompute
        self.error_counts: Dict[str, int] = {k: 0 for k in ERROR_KINDS}
        # prefix-sharing bookkeeping + counters (DESIGN.md
        # §prefix-sharing)
        self._chain_key = [PrefixIndex.ROOT] * B  # parent for next insert
        self._indexed_upto = [0] * B   # aligned tokens already chained
        self._prompt_logits: List[Optional[np.ndarray]] = [None] * B
        self.n_shared_pages = 0
        self.n_shared_tokens = 0
        self.n_full_hits = 0       # whole-prompt matches (prefill skipped)
        self.n_cow_forks = 0
        self.n_reclaimed = 0       # index entries dropped under pressure
        self.n_prefill_chunks = 0
        self.peak_used_pages = 0
        # token-budget scheduler bookkeeping (DESIGN.md §scheduler)
        self.budget_log: List[Dict[str, Any]] = []
        self.n_fused_steps = 0         # prefill chunk rode the decode scan
        self.n_truncated_chunks = 0    # chunks cut at the residual budget
        self._logits = jnp.zeros((B, self.cfg.vocab_size), jnp.float32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._emitted = jnp.zeros((B,), jnp.int32)
        self._max_new = jnp.zeros((B,), jnp.int32)
        self._done = jnp.ones((B,), bool)
        self._trunc = jnp.zeros((B,), bool)
        self._slot_req: List[Optional[Request]] = [None] * B
        # the prompt a slot is actually serving: the request's prompt,
        # plus — for a recompute-preempted victim — the tokens it had
        # already generated, carried as prompt suffix
        self._slot_prompt: List[Optional[np.ndarray]] = [None] * B
        # chunked prefill: prompt tokens already written per slot
        # (None = slot empty or fully prefilled)
        self._prefilled: List[Optional[int]] = [None] * B
        self._pf_next = 0          # round-robin cursor over prefill slots
        # budget scheduler only: while set, _activate defers into this
        # queue instead of arming the slot (see _step_inner_budget —
        # a slot armed between the live-mask snapshot and the decode
        # scan would decode against a garbage block-table row)
        self._activation_queue: Optional[List[tuple]] = None
        _stamp_submitted(requests)
        self._started = True

    def _busy(self) -> bool:
        return bool(self._pending
                    or any(r is not None for r in self._slot_req))

    def _worst_case_pages(self, r: Request) -> int:
        """Pages the request can ever occupy (truncation caps the
        sequence at T).  Invariant under preemption: a recompute
        victim's effective prompt grows by exactly the tokens its
        remaining budget shrinks by, so prompt + max_new is stable."""
        sc = self.sc
        return pages_needed(min(len(r.prompt) + max(r.max_new_tokens, 0),
                                sc.max_seq_len), sc.page_size)

    def _effective_prompt(self, r: Request) -> np.ndarray:
        """The prompt a (re)admission must prefill: the original
        prompt, plus any tokens already generated before a preemption
        (recompute carries them as prompt suffix)."""
        return np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out_tokens, np.int32)])

    # -- failure semantics (DESIGN.md §robustness) --------------------------

    def _fires(self, point: str) -> bool:
        """One hit at a fault-injection point (no-op without an
        injector)."""
        return self.faults is not None and self.faults.fires(point)

    def _fail_request(self, r: Request, kind: str,
                      detail: str = "") -> None:
        """Terminally fail ``r`` with a structured error and unwind it
        from wherever it lives in the lifecycle: pending queue,
        occupied slot (any of mid-prefill / decoding), or the host-RAM
        swap store.  Page references, index pins and the decode mask
        are released exactly as a normal harvest would — the one
        unwind path ``cancel``, deadlines, numerics quarantine and
        terminal swap failure all share."""
        r.error = RequestError(kind=kind, detail=detail,
                               step=self._step_count)
        r.done = True
        self.n_failed += 1
        self.error_counts[kind] += 1
        self._progress = True          # terminal outcome: state moved
        self._retry.pop(id(r), None)
        self._pf_best.pop(id(r), None)
        self._swapped.pop(id(r), None)
        # identity, not ==: Request arrays make __eq__ ambiguous
        self._pending = [p for p in self._pending if p is not r]
        for b in range(self.sc.max_batch):
            if self._slot_req[b] is r:
                self._release(b)
                self._done = self._done.at[b].set(True)
                break

    def cancel(self, rid: int, detail: str = "cancelled by caller"
               ) -> bool:
        """Cancel request ``rid`` at any lifecycle stage — pending,
        mid-prefill, decoding, or swapped out — releasing its pages,
        refcounts and index pins; the rest of the batch is untouched.
        Returns whether a live request was cancelled (False: unknown
        rid, or already terminal)."""
        assert self._started, "call start(requests) first"
        for r in self._all_requests:
            if r.rid == rid and not r.done:
                self._fail_request(r, "cancelled", detail)
                return True
        return False

    def _check_deadlines(self) -> None:
        """Fail requests whose step budget ran out (TTFT: no first
        token yet; total: not done).  Deadlines are engine steps since
        ``start()`` — the scheduler's own clock, so chaos runs
        reproduce deterministically."""
        now = self._step_count
        for r in self._all_requests:
            if r.done:
                continue
            ttft = r.ttft_deadline_steps
            if ttft is not None and not r.out_tokens and now > ttft:
                self._fail_request(
                    r, "deadline",
                    f"no first token after {ttft} steps (TTFT budget)")
            elif r.deadline_steps is not None and now > r.deadline_steps:
                self._fail_request(
                    r, "deadline",
                    f"incomplete after {r.deadline_steps} steps "
                    f"({len(r.out_tokens)}/{r.max_new_tokens} tokens)")

    def _quarantine_nonfinite(self, live: np.ndarray,
                              emits_np: np.ndarray) -> None:
        """NaN/inf logit guard: fail *only* the offending slots
        (error.kind == "numerics") and keep the batch.  The poisoned
        chunk's sampled tokens are discarded for those slots — they
        were drawn from garbage — and their pages go back to the pool
        (never indexed: only a finished harvest leaves index pins)."""
        finite = _to_host(jnp.all(jnp.isfinite(self._logits), axis=-1),
                          "finite")
        for b in np.nonzero(live & ~finite)[0]:
            r = self._slot_req[int(b)]
            emits_np[:, b] = False      # drop this chunk's tokens
            self._fail_request(r, "numerics",
                               "non-finite next-token logits")
            live[b] = False

    # -- prefix sharing (DESIGN.md §prefix-sharing) -------------------------

    def _cap_share(self, L: int, hits, logits):
        """The one shared cap/fork rule for a prefix match (both the
        admission probe and the actual admission use it, so the charge
        check and the charge can never drift): cap the match at
        ``L - 1`` tokens unless terminal logits let the whole prompt be
        served from the index, drop hit pages past the cap, and predict
        the single copy-on-write fork a write landing mid-page in the
        last shared page will need.  Returns
        ``(kept_hits, n_tokens, fork_extra, logits)``."""
        ps = self.sc.page_size
        tokens = sum(n for _, _, n in hits)
        if tokens == L and logits is None:
            tokens = L - 1          # last token recomputed for its logits
        kept = [h for j, h in enumerate(hits) if j * ps < tokens]
        if tokens < L:
            logits = None
        fork = 1 if kept and tokens % ps else 0
        return kept, tokens, fork, logits

    def _probe_share(self, r: Request) -> tuple:
        """Read-only preview of what admission would share for ``r``:
        ``(n_pages, n_tokens, fork_extra, self_pinned)``.
        ``self_pinned`` counts matched pages currently pinned *only* by
        the index: admission would pin them itself, so they must not be
        double-counted as reclaimable headroom in ``_fits_now``."""
        if self._pindex is None or id(r) in self._swapped:
            return 0, 0, 0, 0
        prompt = self._effective_prompt(r)
        L = len(prompt)
        hits, _, _, logits = self._pindex.walk(prompt, self.sc.page_size)
        kept, tokens, fork, _ = self._cap_share(L, hits, logits)
        self_pin = sum(1 for _, p, _ in kept if self.pool.ref(p) == 1)
        return len(kept), tokens, fork, self_pin

    def _alloc(self, n: int) -> List[int]:
        """Pool allocation with index reclamation: pages pinned only by
        the prefix index are dropped (LRU) before the pool can report
        exhaustion — cached prefixes are strictly cheaper to evict than
        live sequences."""
        if n <= 0:
            return []
        if self._pindex is not None and n > self.pool.free_count:
            # prefix_reclaim fault: the pass reclaims nothing (pins
            # that cannot be dropped right now) — callers fall back to
            # their exhaustion handling (retry / preempt)
            if not self._fires("prefix_reclaim"):
                self.n_reclaimed += self._pindex.reclaim(self.pool, n)
        return self.pool.alloc(n)

    def _fork_candidates(self, b: int, lo: int, hi: int) -> List[int]:
        """Logical pages of slot ``b`` that positions [lo, hi) will
        write and that are still shared (refcount > 1): these must be
        copy-on-write forked before the write."""
        if self._pindex is None or hi <= lo:
            return []
        ps = self.sc.page_size
        rows = self._btabs.rows[b]
        n_owned = len(self._btabs.slot_pages[b])
        return [j for j in range(lo // ps, min((hi - 1) // ps, n_owned - 1)
                                 + 1)
                if self.pool.ref(int(rows[j])) > 1]

    def _cow_fork(self, b: int, j: int) -> None:
        """Fork logical page ``j`` of slot ``b``: device page copy into
        a fresh page, row repointed, one reference dropped on the
        original (other sharers and the index keep reading it)."""
        old = int(self._btabs.rows[b, j])
        if self._fires("copy_page"):
            raise PagePoolExhausted("injected copy_page fault")
        new = self._alloc(1)[0]
        self._cache = self._fork_page(self._cache, np.int32(old),
                                      np.int32(new))
        self._btabs.set_page(b, j, new)
        self.pool.free([old])
        self._private[b] += 1
        self.n_cow_forks += 1

    def _late_match(self, b: int) -> bool:
        """Late-binding share at a chunk boundary: map in prompt chunks
        a sibling slot has prefilled (and indexed) *since this slot was
        admitted* — concurrently admitted requests with a common prefix
        find an empty index at admission, so the first slot computes
        each chunk and the rest reference it here instead of
        recomputing.  The slot's never-written private page for that
        logical position is returned to the pool.  Returns True when
        the match completed the whole prompt (terminal logits found —
        the slot is activated and needs no chunk this step)."""
        if self._pindex is None:
            return False
        ps = self.sc.page_size
        prompt = self._slot_prompt[b]
        L = len(prompt)
        start = self._prefilled[b]
        while (start % ps == 0 and start == self._indexed_upto[b]
               and start + ps <= L):
            key = PrefixIndex.child_key(self._chain_key[b],
                                        prompt[start: start + ps])
            hit = self._pindex.get(key)
            if hit is None or hit[0] == int(self._btabs.rows[b, start // ps]):
                break
            page, _, logits = hit
            old = self._btabs.slot_pages[b][start // ps]
            self.pool.share([page])
            self._btabs.set_page(b, start // ps, page)
            self.pool.free([old])
            self._private[b] -= 1
            self.n_shared_pages += 1
            self.n_shared_tokens += ps
            self._chain_key[b] = key
            start += ps
            self._indexed_upto[b] = start
            if start == L:
                if logits is not None:
                    self._prefilled[b] = None
                    self.n_full_hits += 1
                    self._activate(b, self._slot_req[b],
                                   jnp.asarray(logits))
                    return True
                # no stored logits: recompute the last token (its
                # write copy-on-write forks the shared page)
                start -= 1
                break
        self._prefilled[b] = start
        return False

    def _activate(self, b: int, r: Request, last_logits) -> None:
        """Arm slot ``b`` for decode once its prompt cache is in place.

        Under the token-budget scheduler the call may be *deferred*:
        between the step's live-mask snapshot and its decode scan, a
        newly completed slot must stay ``done`` (its block-table row
        exports as garbage to the scan — arming it early would decode
        it into the void and silently burn its budget), so the
        activation lands after the scan and the slot joins decode next
        step, where it is charged like any other decoding slot."""
        if self._activation_queue is not None:
            self._activation_queue.append(
                (b, r, _to_host(last_logits, "logits")))
            return
        self._logits = self._logits.at[b].set(last_logits)
        self._pos = self._pos.at[b].set(len(self._slot_prompt[b]))
        self._emitted = self._emitted.at[b].set(0)
        # a resumed victim already emitted part of its budget
        self._max_new = self._max_new.at[b].set(
            r.max_new_tokens - len(r.out_tokens))
        self._done = self._done.at[b].set(False)
        self._trunc = self._trunc.at[b].set(False)
        if self._pindex is not None:
            # terminal next-token logits: attached to the prompt's
            # index entry at release, so an exact-duplicate prompt can
            # later skip prefill entirely
            self._prompt_logits[b] = _to_host(last_logits, "logits")

    def _index_terminal(self, b: int) -> None:
        """Leave a finished slot's prompt tail in the prefix index
        (before its references are released): the final partial-page
        chunk, if any, plus the prompt's next-token logits.  Entries
        pin their page, so the pages outlive the request for reuse
        until ``reclaim`` drops them under pool pressure."""
        prompt = self._slot_prompt[b]
        if (self._prefilled[b] is not None or prompt is None
                or self._prompt_logits[b] is None):
            return                        # mid-prefill or never activated
        ps = self.sc.page_size
        L = len(prompt)
        k, rem = divmod(L, ps)
        if self._indexed_upto[b] != k * ps:
            return                        # chain incomplete (full pages
        #                                   not all indexed): skip
        if rem:
            key = PrefixIndex.child_key(self._chain_key[b], prompt[k * ps:])
            self._pindex.insert(key, int(self._btabs.rows[b, k]), rem,
                                self.pool, logits=self._prompt_logits[b])
        elif self._chain_key[b] != PrefixIndex.ROOT:
            self._pindex.attach_logits(self._chain_key[b],
                                       self._prompt_logits[b])

    def _release(self, b: int, finished: bool = False) -> None:
        if self.sc.paged and finished and self._pindex is not None:
            self._index_terminal(b)
        self._slot_req[b] = None
        self._slot_prompt[b] = None
        self._prefilled[b] = None
        self._prompt_logits[b] = None
        self._chain_key[b] = PrefixIndex.ROOT
        self._indexed_upto[b] = 0
        if self.sc.paged:
            # page references drop without draining the batch (shared
            # pages survive via their other sharers / the index); the
            # row resets to the garbage page
            self._btabs.release(b, self.pool)
            self._reserved[b] = 0
            self._charged[b] = 0
            self._private[b] = 0

    def _fits_now(self, r: Request, worst_private: int,
                  shared: tuple) -> bool:
        """Whether the request can be admitted at this instant.

        ``worst_private`` and ``shared = (n_pages, n_tokens, fork,
        self_pinned)`` count only the request's *private* tail: pages
        its shared prefix already occupies are charged to nobody (they
        exist once, however many requests share them) — without this,
        a shared-heavy workload re-inherits the pessimistic cap that
        reservation admission was built to avoid.  Index pins the
        request itself would take over (``self_pinned``) are excluded
        from the reclaimable headroom: once matched they are no longer
        reclaimable, so counting them would over-admit and crash the
        private-tail allocation."""
        s_pages, _, s_fork, s_pin = shared
        reclaimable = (self._pindex.reclaimable(self.pool) - s_pin
                       if self._pindex is not None else 0)
        if self.sc.admission == "reserve":
            # every already-admitted slot may still grow by
            # (charged - private) pages; the new request's private
            # worst case must fit what remains after distinct live
            # pages (minus index pins reclaimable on demand) and that
            # outstanding growth
            outstanding = sum(self._charged[s] - self._private[s]
                              for s in range(self.sc.max_batch))
            headroom = (self.pool.n_pages
                        - (self.pool.used_count - reclaimable)
                        - outstanding)
            return worst_private <= headroom
        # optimistic: charge only what materializes right now — the
        # effective prompt's unshared pages (for a swap victim that
        # equals its swapped length) plus a possible copy-on-write
        # fork, capped by the pool's high watermark.  An idle pool
        # always admits a fitting request, or nothing could ever run
        # when the prompt alone crosses the watermark.
        need = (pages_needed(len(r.prompt) + len(r.out_tokens),
                             self.sc.page_size) - s_pages + s_fork)
        avail = self.pool.free_count + reclaimable
        eff_used = self.pool.used_count - reclaimable
        if eff_used == 0:
            return need <= avail
        return need <= avail and eff_used + need <= self.pool.high_pages

    def _next_admissible(self) -> Optional[Request]:
        """Pop the first admissible pending request within the
        ``admit_window`` scan, so a small request is not head-of-line
        blocked behind a big one whose worst case doesn't fit yet.
        Requests that could never fit — worst case beyond the whole
        pool, even drained — are marked failed along the way instead
        of aborting the batch."""
        sc = self.sc
        i = scanned = 0
        while i < len(self._pending) and scanned < sc.admit_window:
            r = self._pending[i]
            rt = self._retry.get(id(r))
            if rt is not None and self._step_count < rt[1]:
                # backing off after a transient admission alloc
                # failure: not eligible again until its retry step
                i += 1
                scanned += 1
                continue
            if r.max_new_tokens - len(r.out_tokens) <= 0:
                # nothing (left) to decode: resolve at admission
                r.done = True
                self._pending.pop(i)
                continue
            if (sc.max_num_batched_tokens
                    and len(r.prompt) > sc.max_seq_len):
                # budget scheduler: an over-long prompt is a structured
                # per-request failure here, not a start()-time abort —
                # the page-pool check below cannot catch it because the
                # worst-case footprint is capped at max_seq_len
                self._fail_request(
                    r, "oversize",
                    f"prompt length {len(r.prompt)} exceeds "
                    f"max_seq_len {sc.max_seq_len}")
                continue
            if sc.paged:
                worst = self._worst_case_pages(r)
                if worst > self.pool.n_pages:
                    # infeasible even alone: its distinct pages (shared
                    # or not) can never fit the pool simultaneously
                    self._fail_request(
                        r, "oversize",
                        f"worst case {worst} pages exceeds the "
                        f"{self.pool.n_pages}-page pool")
                    continue
                shared = self._probe_share(r)
                worst_private = worst - shared[0] + shared[2]
                if not self._fits_now(r, worst_private, shared):
                    i += 1
                    scanned += 1
                    continue
            return self._pending.pop(i)
        return None

    @functools.partial(jax.profiler.annotate_function, name="engine.admit")
    def _admit(self, limit: Optional[int] = None) -> int:
        """Fill free slots from the pending queue; returns how many
        requests were admitted.  ``limit`` caps the count (the budget
        scheduler admits only while total occupancy stays within the
        per-step token budget; None = every free slot).

        Exact-length path: prefill the whole (effective) prompt now
        (one compile per distinct length) and insert.  Chunked path:
        match the longest cached prefix in the index (those pages map
        into the block table by reference — no recompute), allocate
        only the private tail's pages, and queue the slot for
        chunk-by-chunk prefill from the first unshared token —
        ``_prefill_step`` advances it while other slots decode.  A
        whole-prompt match with stored terminal logits skips prefill
        entirely.  Swap victims skip both match and prefill: their
        saved pages are restored byte-exact into private pages."""
        sc = self.sc

        def _occupied() -> int:
            return sum(q is not None for q in self._slot_req)

        occ0 = _occupied()
        for b in range(sc.max_batch):
            if limit is not None and _occupied() - occ0 >= limit:
                break
            if self._slot_req[b] is not None:
                continue
            r = self._next_admissible()
            if r is None:
                break
            prompt = self._effective_prompt(r)
            self._slot_req[b] = r
            self._slot_prompt[b] = prompt
            self._stamp[b] = self._admit_seq
            self._admit_seq += 1
            slog = None
            if sc.paged:
                ps = sc.page_size
                L = len(prompt)
                shared: List[int] = []
                shared_tokens = full_tokens = 0
                chain = PrefixIndex.ROOT
                fork = 0
                if self._pindex is not None and id(r) not in self._swapped:
                    hits, chain, full_tokens, slog = self._pindex.walk(
                        prompt, ps)
                    # same cap/fork rule the admission probe used, so
                    # the charge matches what _fits_now checked
                    kept, shared_tokens, fork, slog = self._cap_share(
                        L, hits, slog)
                    shared = [p for _, p, _ in kept]
                    if shared:
                        self._pindex.touch([k for k, _, _ in kept])
                        self.pool.share(shared)
                self._reserved[b] = self._worst_case_pages(r)
                self._charged[b] = self._reserved[b] - len(shared) + fork
                n_priv = pages_needed(L, ps) - len(shared)
                try:
                    phys = self._alloc(n_priv)
                except PagePoolExhausted:
                    # accounting said it fit but the pool disagrees
                    # (another admission this pass consumed the
                    # headroom, or an injected alloc fault): roll the
                    # admission back and retry with exponential
                    # backoff; a request whose retry budget is spent
                    # fails terminally (pool_exhausted) instead of
                    # waiting forever
                    if shared:
                        self.pool.free(shared)
                    self._slot_req[b] = None
                    self._slot_prompt[b] = None
                    self._reserved[b] = 0
                    self._charged[b] = 0
                    n_tries, _ = self._retry.get(id(r), (0, 0))
                    if n_tries >= sc.admission_retries:
                        self._fail_request(
                            r, "pool_exhausted",
                            f"admission allocation failed "
                            f"{n_tries + 1} times (backoff spent)")
                        continue
                    self._retry[id(r)] = (
                        n_tries + 1,
                        self._step_count + min(1 << n_tries, 32))
                    self.n_retried += 1
                    self._pending.insert(0, r)
                    break
                self._retry.pop(id(r), None)     # clean slate on success
                self.n_shared_pages += len(shared)
                self.n_shared_tokens += shared_tokens
                self._private[b] = n_priv
                self._btabs.assign(b, shared + phys)
                # chain state for indexing this slot's own chunks:
                # _chain_key is the digest at token _indexed_upto
                # (pages up to there are already in the index)
                self._chain_key[b] = chain
                self._indexed_upto[b] = full_tokens
                if id(r) in self._swapped:
                    st = self._swapped.pop(id(r))
                    detail = ""
                    if self._fires("swap_in"):
                        detail = "injected swap_in fault"
                    elif checksum(st["bufs"]) != st["crc"]:
                        detail = "swap buffer failed checksum " \
                                 "verification"
                    if not detail:
                        self._swap_in_slot(b, st["bufs"])
                        self._activate(b, r, jnp.asarray(st["logits"]))
                        self.n_swapped_in += 1
                        continue
                    if not sc.swap_fallback:
                        self._release(b)
                        self._fail_request(r, "swap_failed", detail)
                        continue
                    # degrade to recompute: the pages just assigned
                    # already cover the effective prompt (generated
                    # tokens ride as prompt suffix), so fall through
                    # to the normal prefill path below — greedy
                    # outputs are unchanged, only latency is paid
                    self.n_swap_fallbacks += 1
            if r.t_admitted is None:
                r.t_admitted = time.perf_counter()
            if sc.chunked_prefill:
                if slog is not None:
                    # whole prompt served from the index, next-token
                    # logits included: no prefill chunk at all
                    self._prefilled[b] = None
                    self.n_full_hits += 1
                    self._activate(b, r, jnp.asarray(slog))
                    continue
                # chunks run in _prefill_step, starting past the
                # shared prefix
                self._prefilled[b] = (shared_tokens if sc.paged else 0)
                continue
            plogits, slot_cache = self._prefill(
                self.params, self.proj, jnp.asarray(prompt)[None])
            if sc.paged:
                self._cache = self._paged_insert(
                    self._cache, slot_cache,
                    jnp.asarray(self._btabs.slot_pages[b], jnp.int32))
            else:
                self._cache = self._insert(self._cache, slot_cache,
                                           np.int32(b))
            self._activate(b, r, plogits[0, -1])
        return _occupied() - occ0

    def _prep_chunk(self, b: int, cap: Optional[int] = None):
        """Stage slot ``b``'s next prefill chunk host-side: late-bind
        shared chunks, copy-on-write fork any shared page the chunk
        will write, size the chunk (``cap`` truncates it to the
        residual token budget, sarathi-style) and pad it to its
        bucket.  Returns ``(b, r, start, n, bucket, toks)`` ready for
        dispatch, or None when the slot needs no chunk this pass
        (empty / fully late-matched / fault-delayed / preempted at
        fork / failed at bucketing)."""
        sc = self.sc
        if self._prefilled[b] is None:
            return None
        with TraceAnnotation("engine.prefill", rid=self._slot_req[b].rid,
                             start=self._prefilled[b]):
            if self._late_match(b):
                return None                      # whole prompt mapped in
            if self._fires("prefill_delay"):
                return None  # injected slow prefill: chunk runs later
            r = self._slot_req[b]
            prompt = self._slot_prompt[b]
            start = self._prefilled[b]
            n = min(sc.prefill_chunk, len(prompt) - start)
            if cap is not None and n > cap:
                n = cap                          # residual-budget truncation
                self.n_truncated_chunks += 1
            try:
                # a chunk starting inside a shared page (the first
                # unshared token of a partially-matched prefix) must
                # fork it before writing (DESIGN.md §prefix-sharing)
                for j in self._fork_candidates(b, start, start + n):
                    self._cow_fork(b, j)
            except PagePoolExhausted:
                # optimistic admission may find the pool dry at fork
                # time (another slot's growth won the race): preempt
                # this slot; it requeues and retries when pages free
                self._preempt(b)
                return None
            try:
                bucket = sc.bucket_for(n)
            except ValueError as e:
                # a chunk no bucket holds can never prefill: structured
                # per-request failure, not an engine abort (the scheduler
                # sizes chunks within (0, prefill_chunk], so this is
                # defense in depth against config/bucket drift)
                self._fail_request(r, "oversize", str(e))
                return None
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = prompt[start: start + n]
            return b, r, start, n, bucket, toks

    def _finish_chunk(self, b: int, r: Request, start: int, n: int,
                      bucket: int, last) -> None:
        """Host-side bookkeeping after a staged chunk's device call
        landed (standalone or fused): advance the prefill cursor,
        count watchdog progress, index completed pages, and activate
        the slot for decode when the prompt is fully written."""
        prompt = self._slot_prompt[b]
        self.prefill_chunk_shapes.add(bucket)
        self.n_prefill_chunks += 1
        self._prefilled[b] = start + n
        # watchdog progress is the per-request prefill *high
        # watermark*: re-prefilling after a preemption is thrash,
        # not progress, so only new ground counts
        if start + n > self._pf_best.get(id(r), 0):
            self._pf_best[id(r)] = start + n
            self._progress = True
        if self._pindex is not None:
            # chunks whose pages are now complete become shareable
            ps = self.sc.page_size
            while self._indexed_upto[b] + ps <= self._prefilled[b]:
                j = self._indexed_upto[b] // ps
                key = PrefixIndex.child_key(
                    self._chain_key[b], prompt[j * ps: (j + 1) * ps])
                self._pindex.insert(key, int(self._btabs.rows[b, j]),
                                    ps, self.pool)
                self._chain_key[b] = key
                self._indexed_upto[b] += ps
        if self._prefilled[b] == len(prompt):
            self._prefilled[b] = None        # complete: join decode
            self._activate(b, r, last[0])

    def _dispatch_chunk(self, prep) -> None:
        """Run one staged chunk as its own device call."""
        b, r, start, n, bucket, toks = prep
        with TraceAnnotation("engine.prefill", rid=r.rid, start=start, n=n):
            last, self._cache = self._prefill_chunk(
                self.params, self.proj, self._cache, jnp.asarray(toks),
                jnp.asarray([start], jnp.int32),
                jnp.asarray([n], jnp.int32),
                jnp.asarray(self._btabs.rows[b: b + 1]))
            self._finish_chunk(b, r, start, n, bucket, last)

    def _prefill_step(self, budget: Optional[int] = None) -> int:
        """Advance in-flight chunked prefills by up to ``budget``
        (default ``prefill_chunks_per_step``) chunks, round-robin over
        slots so a long prompt cannot starve another mid-prefill slot.
        Each chunk is padded to its bucket and written straight into
        the slot's pages; the slot joins decode when the last chunk
        lands.  Returns the unspent budget, so the post-harvest refill
        pass shares one per-step bound instead of doubling it.  (The
        token-budget scheduler does not use this: it stages chunks
        against the step's residual token budget in
        ``_step_inner_budget`` instead.)"""
        sc = self.sc
        B = sc.max_batch
        if budget is None:
            budget = sc.prefill_chunks_per_step
        for off in range(B):
            if budget == 0:
                break
            b = (self._pf_next + off) % B
            prep = self._prep_chunk(b)
            if prep is None:
                continue
            self._dispatch_chunk(prep)
            budget -= 1
        self._pf_next = (self._pf_next + 1) % B
        return budget

    def _stage_prefill(self, budget: Optional[int] = None) -> List[tuple]:
        """Stage (without dispatching) up to ``budget`` prefill chunks,
        with ``_prefill_step``'s round-robin order.  The sharded engine
        uses this so every shard's r-th staged chunk can ride one
        sharded device call per round; staging is safe because a pass
        stages at most one chunk per slot and a chunk's page writes
        never touch another slot's staged pages."""
        sc = self.sc
        B = sc.max_batch
        if budget is None:
            budget = sc.prefill_chunks_per_step
        preps: List[tuple] = []
        for off in range(B):
            if budget == 0:
                break
            prep = self._prep_chunk((self._pf_next + off) % B)
            if prep is None:
                continue
            preps.append(prep)
            budget -= 1
        self._pf_next = (self._pf_next + 1) % B
        return preps

    # -- preemption (DESIGN.md §preemption) ---------------------------------

    def _swap_out_slot(self, b: int, n_tokens: int) -> Dict[str, Any]:
        """Copy slot ``b``'s first ``n_tokens`` cache entries of every
        layer to host RAM (before its pages are released)."""
        row = self._btabs.rows[b].copy()
        fetch = functools.partial(_to_host, what="swap")

        def _out0(pool):                     # prefix leaves: (P, ...)
            return swap_out(pool, row, n_tokens, fetch=fetch)

        def _out1(pools):                    # scanned steps: (n_steps, P, ...)
            return np.stack([swap_out(pools[i], row, n_tokens, fetch=fetch)
                             for i in range(pools.shape[0])])

        bufs = {"prefix": jax.tree.map(_out0, self._cache["prefix"])}
        bufs["steps"] = (jax.tree.map(_out1, self._cache["steps"])
                         if self._cache["steps"] is not None else None)
        return bufs

    def _swap_in_slot(self, b: int, bufs: Dict[str, Any]) -> None:
        """Restore a swapped-out cache through slot ``b``'s (fresh)
        block-table row — byte-exact, so generations resume unchanged."""
        row = self._btabs.rows[b].copy()

        def _in0(pool, vals):
            return swap_in(pool, row, vals)

        def _in1(pools, vals):
            return jnp.stack([swap_in(pools[i], row, vals[i])
                              for i in range(pools.shape[0])])

        cache = {"prefix": jax.tree.map(_in0, self._cache["prefix"],
                                        bufs["prefix"])}
        cache["steps"] = (jax.tree.map(_in1, self._cache["steps"],
                                       bufs["steps"])
                          if self._cache["steps"] is not None else None)
        self._cache = cache

    def _corrupt_swap(self, bufs: Dict[str, Any]) -> Dict[str, Any]:
        """Deterministically bit-flip one byte of the first leaf of a
        swapped buffer (the ``swap_corrupt`` fault: the flip happens
        *after* the checksum was recorded, so swap-in detects it)."""
        leaves, treedef = jax.tree.flatten(bufs)
        leaves[0] = self.faults.corrupt("swap_corrupt",
                                        np.asarray(leaves[0]))
        return jax.tree.unflatten(treedef, leaves)

    def _preempt(self, b: int) -> None:
        """Evict slot ``b`` and requeue its request at the head of the
        pending queue.  Recompute mode (and any mid-prefill victim,
        which has no decode state to save) relies on the generated
        tokens carried as prompt suffix; swap mode saves the slot's
        pages and next-token logits so readmission restores them
        byte-exact instead of recomputing."""
        r = self._slot_req[b]
        mid_prefill = self._prefilled[b] is not None
        if self.sc.preempt_mode == "swap" and not mid_prefill:
            pos = int(_to_host(self._pos, "pos")[b])  # len(prompt)
            try:
                if self._fires("swap_out"):
                    raise SwapFailed("injected swap_out fault")
                bufs = self._swap_out_slot(b, pos)
                # integrity receipt: swap-in re-checks it before
                # restoring, so a corrupted host buffer degrades to
                # recompute instead of silently resuming from garbage
                crc = checksum(bufs)
                if self._fires("swap_corrupt"):
                    bufs = self._corrupt_swap(bufs)
                self._swapped[id(r)] = {
                    "logits": _to_host(self._logits[b], "logits"),
                    "bufs": bufs,
                    "crc": crc,
                }
                self.n_swapped_out += 1
            except SwapFailed:
                # nothing saved: the victim requeues in recompute
                # mode — its generated tokens ride as prompt suffix
                self.n_swap_fallbacks += 1
        self._pending.insert(0, r)
        self._release(b)
        self._done = self._done.at[b].set(True)
        self.n_preempted += 1
        self.preempted_rids.append(r.rid)

    def _preempt_for_headroom(self, live: np.ndarray,
                              needs: Dict[int, int]) -> None:
        """Free pages for this chunk's growth, cheapest first: cached
        prefix pages only the index pins are reclaimed (LRU), then
        victims are evicted by (priority, LIFO stamp) — lowest
        ``Request.priority`` first, youngest admission stamp within a
        tier, so a high-priority request is preempted only when no
        lower tier is left to evict.

        ``needs``: extra pages per live slot.  Victims are *any*
        occupied slot (decoding or mid-prefill), and the best-ranked
        slot (highest priority, oldest) is never evicted — combined
        with the fail-at-admission check (worst case <= whole pool)
        that guarantees forward progress: at minimum that request runs
        alone.  Eviction continues past the strict deficit until
        ``low_extra`` slack pages are also free (thrash guard)."""
        deficit = sum(needs.values())
        if self._pindex is not None and deficit > self.pool.free_count:
            if not self._fires("prefix_reclaim"):
                self.n_reclaimed += self._pindex.reclaim(self.pool,
                                                         deficit)
        if deficit <= self.pool.free_count:
            return
        cand = sorted((b for b in range(self.sc.max_batch)
                       if self._slot_req[b] is not None),
                      key=lambda b: (-self._slot_req[b].priority,
                                     self._stamp[b]))
        while len(cand) > 1 and (deficit + self.pool.low_extra
                                 > self.pool.free_count):
            b = cand.pop()           # lowest priority, youngest stamp
            deficit -= needs.pop(b, 0)
            self._preempt(b)
            live[b] = False

    @functools.partial(jax.profiler.annotate_function, name="engine.headroom")
    def _ensure_chunk_headroom(self, live: np.ndarray) -> None:
        """Grow live sequences page-by-page: every decoding slot gets
        pages covering the next ``decode_chunk`` tokens before the
        fused scan runs (the scan itself never allocates), and any
        still-shared page the chunk will write into is copy-on-write
        forked first (a sharer diverging mid-decode writes a private
        copy; the other sharers keep reading the original).  Reserve
        admission guarantees the allocations succeed (forks are part
        of the private-tail charge); optimistic admission instead
        reclaims index pins and preempts victims when the pool would
        run dry.  Mid-prefill slots are skipped — their prompt pages
        were allocated at admission and they grow only once they join
        decode."""
        sc = self.sc
        pos_np = _to_host(self._pos, "pos")
        needs: Dict[int, int] = {}
        grow: Dict[int, int] = {}
        forks: Dict[int, List[int]] = {}
        for b in range(sc.max_batch):
            if not live[b]:
                continue
            end = min(int(pos_np[b]) + sc.decode_chunk, sc.max_seq_len)
            need = min(pages_needed(end, sc.page_size), self._reserved[b])
            extra = need - len(self._btabs.slot_pages[b])
            nf = self._fork_candidates(b, int(pos_np[b]), end)
            if extra > 0:
                grow[b] = extra
            if nf:
                forks[b] = nf
            tot = max(extra, 0) + len(nf)
            if tot > 0:
                needs[b] = tot
        if sc.admission == "optimistic":
            self._preempt_for_headroom(live, needs)
        for b, pages in forks.items():
            if not live[b]:                  # evicted above
                continue
            try:
                for j in pages:
                    if self.pool.ref(int(self._btabs.rows[b, j])) > 1:
                        self._cow_fork(b, j)  # sharer may be evicted
            except PagePoolExhausted:
                # pool dry at fork time (exhaustion race or injected
                # fault): preempt the would-be writer; it requeues and
                # retries when pages free up
                self._preempt(b)
                live[b] = False
        for b, extra in grow.items():
            if not live[b]:
                continue
            have = len(self._btabs.slot_pages[b])
            try:
                phys = self._alloc(extra)
            except PagePoolExhausted:
                # growth allocation failed (race / injected): evict
                # this slot rather than abort the batch — reserve
                # admission makes this unreachable without injection
                self._preempt(b)
                live[b] = False
                continue
            self._btabs.assign(b, phys, start=have)
            # grown pages are private: without this the reserve-mode
            # outstanding-growth sum double-counts them (once in
            # used_count, once in charged - private) and admission
            # turns pessimistic as sequences decode
            self._private[b] += extra

    def step(self) -> bool:
        """One scheduling iteration: admit, advance chunked prefills,
        run one fused decode chunk over the decodable slots, harvest —
        then admit again, so a slot freed by the harvest starts its
        next request in the *same* step instead of idling for a full
        chunk (the refill-bubble fix).  Returns whether any work
        remains (the ``generate`` drain condition).

        Wraps the scheduling body with the robustness rails
        (DESIGN.md §robustness): per-request deadlines are checked
        before scheduling, ``invariants.audit`` runs after it on every
        ``ServeConfig.audit_every``-th step (``ServeConfig.audit``;
        the counter is ``n_audits``), and a no-progress watchdog turns
        ``stall_steps`` consecutive do-nothing iterations (no new
        prefill ground, no emitted tokens, no terminal outcomes) into
        ``EngineStalledError`` instead of spinning ``generate``
        forever."""
        assert self._started, "call start(requests) first"
        self._step_count += 1
        with TraceAnnotation("engine.step", step=self._step_count):
            self._progress = False
            self._check_deadlines()
            busy = self._step_inner()
            if (self.sc.audit
                    and self._step_count % self.sc.audit_every == 0):
                invariants.audit(self)
                self.n_audits += 1
            if busy and not self._progress:
                self._no_progress += 1
                if (self.sc.stall_steps
                        and self._no_progress >= self.sc.stall_steps):
                    raise EngineStalledError(
                        self._no_progress, invariants.scheduler_dump(self))
            else:
                self._no_progress = 0
        return busy

    def _step_inner(self) -> bool:
        sc = self.sc
        if sc.max_num_batched_tokens:
            return self._step_inner_budget()
        B = sc.max_batch
        self._admit()
        if sc.paged:
            self.peak_used_pages = max(self.peak_used_pages,
                                       self.pool.used_count)
        pf_budget = 0
        if sc.chunked_prefill:
            pf_budget = self._prefill_step()
        # decodable = admitted and fully prefilled; mid-prefill slots
        # hold their pages and join decode only when complete
        live = np.array([self._slot_req[b] is not None
                         and self._prefilled[b] is None
                         for b in range(B)])
        if not live.any():
            return self._busy()
        btab_dev = None
        if sc.paged:
            # may preempt LIFO victims (optimistic admission) when the
            # chunk's growth would exhaust the pool — mutates ``live``
            self._ensure_chunk_headroom(live)
            if not live.any():
                return self._busy()
            # mid-prefill / evicted rows export as garbage so the
            # scan's masked writes cannot touch pages a prefill is
            # filling or that were recycled
            btab_dev = self._btabs.device(live=live)
            self.peak_used_pages = max(self.peak_used_pages,
                                       self.pool.used_count)
        num_splits = self._live_splits(live)
        with TraceAnnotation("engine.dispatch", live=int(live.sum())):
            carry, toks, emits = self._decode_chunk(
                self.params, self.proj, self._cache, self._logits,
                self._pos, self._emitted, self._max_new, self._done,
                self._trunc, self.rng, btab_dev, num_splits=num_splits)
        (self._logits, self._cache, self._pos, self._emitted, self._done,
         self._trunc, self.rng) = carry
        with TraceAnnotation("engine.harvest"):
            freed = self._harvest(live, toks, emits)
        if freed and self._pending:
            # refill the freed slots now: the next request prefills in
            # this very step instead of sitting idle for one chunk
            # (within the step's remaining prefill-chunk budget)
            self._admit()
            if sc.chunked_prefill and pf_budget:
                self._prefill_step(pf_budget)
        return self._busy()

    def _harvest(self, live: np.ndarray, toks, emits) -> bool:
        """Collect one decode chunk's outcomes host-side: append the
        emitted tokens to their requests, quarantine non-finite slots,
        and release slots whose request finished.  Returns whether any
        slot was freed (the same-step refill trigger)."""
        sc = self.sc
        toks_np = _to_host(toks, "toks")      # (N, B)
        # writable: quarantine masks poisoned slots
        emits_np = _to_host(emits, "emits", copy=True)
        if self._fires("nan_logits"):
            # kernel numerics fault: poison the lowest live slot's
            # next-token logits (the guard below quarantines it)
            b0 = int(np.nonzero(live)[0][0])
            self._logits = self._logits.at[b0].set(jnp.nan)
        if sc.guard_numerics:
            self._quarantine_nonfinite(live, emits_np)
        if emits_np[:, live].any():
            self._progress = True
        done_np = _to_host(self._done, "done")
        trunc_np = _to_host(self._trunc, "trunc")
        now = time.perf_counter()
        freed = False
        for b in range(sc.max_batch):
            if not live[b]:
                continue
            r = self._slot_req[b]
            r.out_tokens.extend(
                int(toks_np[t, b]) for t in range(sc.decode_chunk)
                if emits_np[t, b])
            if r.t_first_token is None and r.out_tokens:
                r.t_first_token = now
            if done_np[b]:
                r.done = True
                r.truncated = bool(trunc_np[b])
                self._release(b, finished=True)
                self._retry.pop(id(r), None)
                self._pf_best.pop(id(r), None)
                self.n_completed += 1
                freed = True
        return freed

    def _step_inner_budget(self) -> bool:
        """One token-budget scheduling iteration (DESIGN.md §scheduler,
        ``ServeConfig.max_num_batched_tokens > 0``).

        The step builds a single token budget and spends it in a fixed
        order: (1) every decodable slot charges one token (they were
        admitted in earlier steps and cannot be deferred without
        stalling their streams); (2) admission fills free slots only
        while total occupancy stays within the budget, since every
        occupied slot is a future per-step decode charge; (3) prefill
        chunks fill the residual round-robin, the last chunk truncated
        to whatever remains (sarathi-style) instead of skipping the
        step.  One staged chunk then *fuses* into the decode dispatch
        (``_fused_step``) so the prompt rides the decode batch's
        memory-bound iteration; any further staged chunks (and all
        chunks on steps with nothing decoding) dispatch standalone.
        Per-step device work is thereby bounded by
        ``max_num_batched_tokens`` whatever the prefill:decode mix —
        the legacy path's cost instead grows with
        ``prefill_chunks_per_step`` full chunks on top of the scan."""
        sc = self.sc
        B = sc.max_batch
        budget = sc.max_num_batched_tokens
        # (1) decode charges first
        live = np.array([self._slot_req[b] is not None
                         and self._prefilled[b] is None
                         for b in range(B)])
        if live.any():
            # may preempt LIFO victims (optimistic admission) when the
            # chunk's growth would exhaust the pool — mutates ``live``
            self._ensure_chunk_headroom(live)
        n_decode = int(live.sum())
        residual = max(budget - n_decode, 0)
        # slots completing from here to the scan (chunk landed, late
        # prefix match, swap-in restore) defer their activation: the
        # scan must not decode a slot the live mask snapshotted as
        # non-decodable (its row exports as garbage)
        self._activation_queue = queue = []
        # (2) admission under the same budget
        n_occ = sum(q is not None for q in self._slot_req)
        n_admitted = self._admit(limit=max(budget - n_occ, 0))
        self.peak_used_pages = max(self.peak_used_pages,
                                   self.pool.used_count)
        # (3) prefill chunks fill the residual
        chunks: List[tuple] = []
        spent_pf = 0
        for off in range(B):
            if residual - spent_pf <= 0:
                break
            prep = self._prep_chunk((self._pf_next + off) % B,
                                    cap=residual - spent_pf)
            if prep is None:
                continue
            chunks.append(prep)
            spent_pf += prep[3]
        self._pf_next = (self._pf_next + 1) % B
        fused = chunks.pop(0) if (live.any() and chunks) else None
        for prep in chunks:
            self._dispatch_chunk(prep)
        freed = False
        if live.any():
            # mid-prefill / evicted rows export as garbage so the
            # scan's masked writes cannot touch pages a prefill is
            # filling or that were recycled — which is also what makes
            # fusing the chunk into the same dispatch safe
            btab_dev = self._btabs.device(live=live)
            self.peak_used_pages = max(self.peak_used_pages,
                                       self.pool.used_count)
            num_splits = self._live_splits(live)
            dispatch = TraceAnnotation("engine.dispatch",
                                       live=int(live.sum()))
            if fused is not None:
                fb, fr, fstart, fn, fbucket, ftoks = fused
                with dispatch:
                    last, carry, toks, emits = self._fused_step(
                        self.params, self.proj, self._cache,
                        jnp.asarray(ftoks),
                        jnp.asarray([fstart], jnp.int32),
                        jnp.asarray([fn], jnp.int32),
                        jnp.asarray(self._btabs.rows[fb: fb + 1]),
                        self._logits, self._pos, self._emitted,
                        self._max_new, self._done, self._trunc, self.rng,
                        btab_dev, num_splits=num_splits)
                (self._logits, self._cache, self._pos, self._emitted,
                 self._done, self._trunc, self.rng) = carry
                # after the carry unpack: activation must overwrite
                # the stale decode logits for the finishing slot
                with TraceAnnotation("engine.prefill", rid=fr.rid,
                                     start=fstart, n=fn):
                    self._finish_chunk(fb, fr, fstart, fn, fbucket, last)
                self.n_fused_steps += 1
            else:
                with dispatch:
                    carry, toks, emits = self._decode_chunk(
                        self.params, self.proj, self._cache, self._logits,
                        self._pos, self._emitted, self._max_new,
                        self._done, self._trunc, self.rng, btab_dev,
                        num_splits=num_splits)
                (self._logits, self._cache, self._pos, self._emitted,
                 self._done, self._trunc, self.rng) = carry
            with TraceAnnotation("engine.harvest"):
                freed = self._harvest(live, toks, emits)
        # flush deferred activations: the armed slots join decode next
        # step (and are charged there); a slot unwound since queueing
        # (failed / preempted mid-step) is skipped
        self._activation_queue = None
        for qb, qr, qlog in queue:
            if self._slot_req[qb] is qr:
                self._activate(qb, qr, jnp.asarray(qlog))
        self.budget_log.append({
            "step": self._step_count, "budget": budget,
            "n_decode": n_decode, "prefill_tokens": spent_pf,
            "admitted": n_admitted, "fused": fused is not None})
        if freed and self._pending:
            # same-step refill under the same occupancy cap; the new
            # request's prefill starts next step (this step's residual
            # is already spent)
            n_occ = sum(q is not None for q in self._slot_req)
            self._admit(limit=max(budget - n_occ, 0))
        return self._busy()

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a list of requests to completion (continuous batching)."""
        self.start(requests)
        while self.step():
            pass
        return requests

    def lower_decode(self):
        """The fused decode dispatch ``step()`` runs, lowered at the
        started engine's state shapes — for compile checks such as
        whether a TPU build carries its Pallas kernels."""
        assert self._started, "call start(requests) first"
        btab = self._btabs.device() if self.sc.paged else None
        return self._decode_chunk.lower(
            self.params, self.proj, self._cache, self._logits, self._pos,
            self._emitted, self._max_new, self._done, self._trunc,
            self.rng, btab, num_splits=self._decode_splits)


# ---------------------------------------------------------------------------
# Data-axis sharded engine (DESIGN.md §sharded-engine)
# ---------------------------------------------------------------------------


class PooledPages:
    """Read-only aggregate view over the shard-local page pools.

    The sharded engine's ``pool`` attribute for introspection (tests,
    benches, the serve CLI): counts sum over every worker's pool.
    Allocation never goes through this view — pages are owned and
    allocated strictly per shard."""

    def __init__(self, workers):
        self._workers = workers

    @property
    def n_pages(self) -> int:
        """Total allocatable physical pages across every shard."""
        return sum(w.pool.n_pages for w in self._workers)

    @property
    def free_count(self) -> int:
        """Free pages summed over the shard pools."""
        return sum(w.pool.free_count for w in self._workers)

    @property
    def used_count(self) -> int:
        """Allocated pages summed over the shard pools."""
        return sum(w.pool.used_count for w in self._workers)

    @property
    def high_pages(self) -> int:
        """Admission high-watermark page budget summed over shards."""
        return sum(w.pool.high_pages for w in self._workers)


def pick_shard(workers, capacity=None):
    """Route target for the next pending request (the thin global
    admission layer, DESIGN.md §sharded-engine): among workers with
    routing capacity — free slots not already spoken for by their
    local backlog (preemption requeues) — the one with the most
    admission headroom: free pages capped at the high-watermark
    budget, so a pool already past its watermark does not look
    attractive just because another shard is fuller.  Ties break on
    the lower shard index (determinism).  ``capacity`` lets the
    routing loop thread residual per-worker capacities; by default it
    is derived from the worker's slots and backlog.  Returns None when
    no worker has capacity: the head request waits, preserving global
    FIFO order."""
    if capacity is None:
        capacity = [sum(q is None for q in w._slot_req) - len(w._pending)
                    for w in workers]
    best, best_score = None, -1
    for i, w in enumerate(workers):
        if capacity[i] <= 0:
            continue
        score = min(w.pool.free_count,
                    max(w.pool.high_pages - w.pool.used_count, 0))
        if score > best_score:
            best, best_score = w, score
    return best


class _ShardWorker(ServingEngine):
    """One shard's host-local scheduler inside a sharded engine.

    A full ``ServingEngine`` over the shard's slice of the slot axis:
    it owns every piece of host scheduling state — local pending queue
    (preemption requeues stay shard-local), page pool with local
    physical ids, block tables, prefix index, swap store, fault
    injector, counters.  Its *device* state is a view into the
    parent's globally sharded arrays: the properties below route every
    read/write of the decode state, the sampling key and the paged
    cache through the parent's slice, so scheduling code inherited
    from the base class runs unchanged while the bytes stay on the
    shard's device.  Workers never dispatch decode or prefill from
    ``step()`` themselves — the parent batches both across shards into
    single ``shard_map`` calls."""

    def __init__(self, parent, shard: int, cfg, params, sc, projections,
                 faults):
        # the routed properties dereference the parent, so these must
        # exist before base __init__ assigns self.rng through one
        self._parent = parent
        self._shard = shard
        self._base = shard * sc.max_batch
        super().__init__(cfg, params, sc, projections=projections,
                         faults=faults)

    def _gs(self) -> slice:
        """This shard's slice of the global slot axis."""
        return slice(self._base, self._base + self.sc.max_batch)

    @property
    def _logits(self):
        return self._parent._g_logits[self._gs()]

    @_logits.setter
    def _logits(self, val):
        p = self._parent
        p._g_logits = p._g_logits.at[self._gs()].set(val)

    @property
    def _pos(self):
        return self._parent._g_pos[self._gs()]

    @_pos.setter
    def _pos(self, val):
        p = self._parent
        p._g_pos = p._g_pos.at[self._gs()].set(val)

    @property
    def _emitted(self):
        return self._parent._g_emitted[self._gs()]

    @_emitted.setter
    def _emitted(self, val):
        p = self._parent
        p._g_emitted = p._g_emitted.at[self._gs()].set(val)

    @property
    def _max_new(self):
        return self._parent._g_max_new[self._gs()]

    @_max_new.setter
    def _max_new(self, val):
        p = self._parent
        p._g_max_new = p._g_max_new.at[self._gs()].set(val)

    @property
    def _done(self):
        return self._parent._g_done[self._gs()]

    @_done.setter
    def _done(self, val):
        p = self._parent
        p._g_done = p._g_done.at[self._gs()].set(val)

    @property
    def _trunc(self):
        return self._parent._g_trunc[self._gs()]

    @_trunc.setter
    def _trunc(self, val):
        p = self._parent
        p._g_trunc = p._g_trunc.at[self._gs()].set(val)

    @property
    def rng(self):
        """This shard's sampling key: row ``shard`` of the parent's
        (shards, 2) stacked key array (decorrelated per-shard seeds)."""
        return self._parent._g_rng[self._shard]

    @rng.setter
    def rng(self, val):
        p = self._parent
        p._g_rng = p._g_rng.at[self._shard].set(val)

    @property
    def _cache(self):
        return self._parent._slice_cache(self._shard)

    @_cache.setter
    def _cache(self, val):
        self._parent._merge_cache(self._shard, val)


class ShardedServingEngine(ServingEngine):
    """Data-axis sharded serving engine (DESIGN.md §sharded-engine).

    ``ServingEngine`` construction routes here when
    ``ServeConfig.shards > 1`` (so ``shards == 1`` never touches this
    code and the single-device engine stays the bitwise parity
    oracle).  The slot axis is cut into ``shards`` contiguous slices,
    one ``_ShardWorker`` per slice; each worker schedules host-locally
    — admission, chunked prefill staging, preemption, prefix sharing,
    swap and fault injection all operate on its own slots and its own
    page pool — while the device state (decode arrays, sampling keys,
    page pools) lives in globally sharded arrays laid over a
    ``("data",)`` mesh (``partition.serve_mesh``).  Each step runs at
    most one sharded prefill round per staged chunk and one sharded
    decode scan, dispatched with ``shard_map``: every shard computes
    on its local slice against its local page pool, so there are no
    gathers and no collectives on the hot path.

    A thin global admission layer on top routes pending requests, in
    strict queue order, to the shard ``pick_shard`` selects
    (watermark-aware most-free-pages, head-of-line blocking preserves
    priority order inside each shard's admit window).  Greedy decoding
    is batch-composition invariant, so ``shards = N`` reproduces the
    ``shards = 1`` outputs token-for-token."""

    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig,
                 projections: Optional[ModelProjections] = None,
                 faults: Optional[FaultInjector] = None):
        super().__init__(cfg, params, sc, projections=projections,
                         faults=faults)
        sc = self.sc
        S = sc.shards
        self._mesh = partition.serve_mesh(S)
        # weights and projections replicate onto every shard's device
        # once; left on one device, each dispatch would send them again
        rep = partition.named(self._mesh)
        self.params = jax.device_put(self.params, rep)
        self.proj = jax.device_put(self.proj, rep)
        # per-shard sampling keys must exist before the workers: base
        # __init__ assigns worker.rng through the routed property
        self._g_rng = jnp.stack(
            [jax.random.PRNGKey(sc.seed + s) for s in range(S)])

        def _local_sc(s: int) -> ServeConfig:
            kw: Dict[str, Any] = dict(
                shards=1,
                max_batch=sc.max_batch // S,
                n_pages=sc.total_pages // S,
                seed=sc.seed + s)
            if sc.chaos_seed is not None:
                # decorrelated chaos schedules: each shard draws its
                # own fault sequence, still reproducible from the seed
                kw["chaos_seed"] = sc.chaos_seed + s
            return dataclasses.replace(sc, **kw)

        self.workers = [
            _ShardWorker(self, s, cfg, params, _local_sc(s), projections,
                         faults)
            for s in range(S)]
        # every worker's cache slice has identical shapes: share one
        # compiled COW fork instead of tracing it per shard
        for w in self.workers[1:]:
            w._fork_page = self.workers[0]._fork_page
        self._local_phys = self.workers[0]._pool_pages()
        self._sharded_prefill = jax.jit(self._sharded_prefill_impl)
        self._sharded_decode = jax.jit(self._sharded_decode_impl,
                                       static_argnames=("num_splits",))

    #: scheduler counters transparently summed over the shard workers
    #: on read (each worker counts its own slots; the aggregate is the
    #: engine-level number tests and benches expect)
    _AGG_COUNTERS = (
        "n_completed", "n_preempted", "n_swapped_out", "n_swapped_in",
        "n_retried", "n_swap_fallbacks", "n_reclaimed", "n_cow_forks",
        "n_shared_pages", "n_shared_tokens", "n_full_hits",
        "n_prefill_chunks", "n_fused_steps", "n_truncated_chunks",
        "peak_used_pages")

    def __getattr__(self, name):
        """Aggregate per-shard scheduler counters on read: plain sums
        for ``_AGG_COUNTERS``, merged dict for ``error_counts``,
        concatenation for ``preempted_rids``; ``n_failed`` adds
        failures of requests still in the global queue (deadline
        before routing)."""
        workers = self.__dict__.get("workers")
        if workers:
            if name in ShardedServingEngine._AGG_COUNTERS:
                return sum(getattr(w, name) for w in workers)
            if name == "preempted_rids":
                return [rid for w in workers for rid in w.preempted_rids]
            if name == "n_failed":
                return (self.__dict__.get("_n_failed_global", 0)
                        + sum(w.n_failed for w in workers))
            if name == "error_counts":
                out = dict(self.__dict__.get("_error_counts_global")
                           or {k: 0 for k in ERROR_KINDS})
                for w in workers:
                    for k, v in w.error_counts.items():
                        out[k] = out.get(k, 0) + v
                return out
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    # -- global cache layout -------------------------------------------------

    def _cache_spec(self):
        """``shard_map`` partition-spec tree for the global paged
        cache: prefix leaves shard their page axis (dim 0), scanned
        step leaves shard dim 1 (dim 0 is the scan-stacked layers)."""
        return {"prefix": P("data"), "steps": P(None, "data")}

    def _slice_cache(self, s: int):
        """Shard ``s``'s local cache view: its ``local_phys + 1`` page
        slice (garbage page included) of every pool leaf."""
        lo = s * (self._local_phys + 1)
        hi = lo + self._local_phys + 1

        def _s0(leaf):
            return leaf[lo:hi]

        def _s1(leaf):
            return leaf[:, lo:hi]

        g = self._g_cache
        return {"prefix": jax.tree.map(_s0, g["prefix"]),
                "steps": (jax.tree.map(_s1, g["steps"])
                          if g["steps"] is not None else None)}

    def _merge_cache(self, s: int, local) -> None:
        """Write shard ``s``'s local cache view back into the global
        pools (the worker ``_cache`` property setter: swap-ins, COW
        forks and slot inserts land here)."""
        lo = s * (self._local_phys + 1)
        hi = lo + self._local_phys + 1

        def _m0(leaf, lleaf):
            return leaf.at[lo:hi].set(lleaf.astype(leaf.dtype))

        def _m1(leaf, lleaf):
            return leaf.at[:, lo:hi].set(lleaf.astype(leaf.dtype))

        g = self._g_cache
        self._g_cache = {
            "prefix": jax.tree.map(_m0, g["prefix"], local["prefix"]),
            "steps": (jax.tree.map(_m1, g["steps"], local["steps"])
                      if g["steps"] is not None else None)}

    # -- sharded device dispatch --------------------------------------------

    def _sharded_prefill_impl(self, params, proj, cache, tokens, pos0,
                              n_valid, rows):
        """One prefill round over every shard as a single ``shard_map``
        computation: shard ``s`` runs the ordinary
        ``_prefill_chunk_impl`` on its (1, bucket) token slice against
        its local page slice — shard-local, no collectives.  Shards
        with no staged chunk this round carry a dummy row
        (``n_valid == 0``, all-garbage block-table row): their writes
        route to the shard's garbage page and the returned logits are
        discarded."""
        d = P("data")

        def _body(cache, tokens, pos0, n_valid, rows):
            return self._prefill_chunk_impl(params, proj, cache, tokens,
                                            pos0, n_valid, rows)

        with jax.named_scope("sharded_prefill"):
            return jax.shard_map(
                _body, mesh=self._mesh,
                in_specs=(self._cache_spec(), d, d, d, d),
                out_specs=(d, self._cache_spec()),
                check_vma=False)(cache, tokens, pos0, n_valid, rows)

    def _sharded_decode_impl(self, params, proj, cache, logits, pos,
                             emitted, max_new, done, trunc, rngs,
                             block_table, num_splits=1):
        """The fused decode scan over every shard as a single
        ``shard_map`` computation: shard ``s`` runs the ordinary
        ``_decode_chunk_impl`` on its slot slice with its own sampling
        key against its local page slice.  Block-table rows hold
        *local* physical ids, so no index translation (and no gather)
        happens on the hot path; shards whose slots are all done take
        the scan's cheap skip branch."""
        d = P("data")
        cspec = self._cache_spec()

        def _body(cache, logits, pos, emitted, max_new, done, trunc,
                  rngs, block_table):
            carry, toks, emits = self._decode_chunk_impl(
                params, proj, cache, logits, pos, emitted, max_new,
                done, trunc, rngs[0], block_table, num_splits)
            (logits, cache, pos, emitted, done, trunc, rng) = carry
            return (logits, cache, pos, emitted, done, trunc, rng[None],
                    toks, emits)

        with jax.named_scope("sharded_decode"):
            return jax.shard_map(
                _body, mesh=self._mesh,
                in_specs=(cspec, d, d, d, d, d, d, d, d),
                out_specs=(d, cspec, d, d, d, d, d, P(None, "data"),
                           P(None, "data")),
                check_vma=False)(cache, logits, pos, emitted, max_new,
                                 done, trunc, rngs, block_table)

    # -- lifecycle -----------------------------------------------------------

    def start(self, requests: List[Request]) -> None:
        """Initialize sharded serving state for a batch of requests.

        Allocates the globally sharded decode arrays and page pools on
        the ``("data",)`` mesh, then starts every shard worker empty —
        requests enter through the global router at the first
        ``step()``."""
        sc = self.sc
        S = sc.shards
        B, T = sc.max_batch, sc.max_seq_len
        for r in requests:
            if len(r.prompt) > T:
                raise ValueError(
                    f"request {r.rid}: prompt length {len(r.prompt)}"
                    f" exceeds max_seq_len {T}")
        self._pending = list(requests)        # global queue, pre-routing
        self._all_requests = list(requests)
        # parent-level injector resolution mirrors the base engine for
        # introspection; the *workers* own actual injection (an
        # explicit injector is shared, a chaos schedule is rebuilt
        # per-shard from decorrelated seeds)
        if self._faults_arg is not None:
            self.faults = self._faults_arg
        elif sc.chaos_seed is not None:
            self.faults = FaultInjector.chaos(sc.chaos_seed,
                                              sc.chaos_rate)
        else:
            self.faults = None
        mesh = self._mesh
        Pl = self._local_phys

        def _put(x):
            return jax.device_put(x, partition.slot_sharding(mesh, x.ndim))

        self._g_logits = _put(jnp.zeros((B, self.cfg.vocab_size),
                                        jnp.float32))
        self._g_pos = _put(jnp.zeros((B,), jnp.int32))
        self._g_emitted = _put(jnp.zeros((B,), jnp.int32))
        self._g_max_new = _put(jnp.zeros((B,), jnp.int32))
        self._g_done = _put(jnp.ones((B,), bool))
        self._g_trunc = _put(jnp.zeros((B,), bool))
        self._g_rng = _put(self._g_rng)
        cache = self.model.init_paged_cache(S * (Pl + 1), sc.page_size,
                                            self.ranks)

        def _put1(leaf):
            return jax.device_put(leaf, partition.named(mesh, None, "data"))

        self._g_cache = {
            "prefix": jax.tree.map(_put, cache["prefix"]),
            "steps": (jax.tree.map(_put1, cache["steps"])
                      if cache["steps"] is not None else None)}
        for w in self.workers:
            w.start([])
        self.pool = PooledPages(self.workers)
        self._n_failed_global = 0
        self._error_counts_global = {k: 0 for k in ERROR_KINDS}
        self._progress_global = False
        self._step_count = 0
        self._no_progress = 0
        self.n_audits = 0
        _stamp_submitted(requests)
        self._started = True

    def _busy(self) -> bool:
        return bool(self._pending) or any(w._busy() for w in self.workers)

    def _fail_global(self, r: Request, kind: str, detail: str = "") -> None:
        """Terminally fail a request still waiting in the global queue
        (it was never routed, so no shard state needs unwinding)."""
        r.error = RequestError(kind=kind, detail=detail,
                               step=self._step_count)
        r.done = True
        self._n_failed_global += 1
        self._error_counts_global[kind] += 1
        self._progress_global = True
        self._pending = [p for p in self._pending if p is not r]

    def _check_global_deadlines(self) -> None:
        """Deadline pass for requests not yet routed to a shard (the
        workers check their own requests with the base logic)."""
        now = self._step_count
        for r in list(self._pending):
            ttft = r.ttft_deadline_steps
            if ttft is not None and not r.out_tokens and now > ttft:
                self._fail_global(
                    r, "deadline",
                    f"no first token after {ttft} steps (TTFT budget)")
            elif r.deadline_steps is not None and now > r.deadline_steps:
                self._fail_global(
                    r, "deadline",
                    f"incomplete after {r.deadline_steps} steps "
                    f"({len(r.out_tokens)}/{r.max_new_tokens} tokens)")

    def cancel(self, rid: int, detail: str = "cancelled by caller"
               ) -> bool:
        """Cancel request ``rid``: unrouted requests fail in the global
        queue; routed ones delegate to their owning shard's unwind."""
        assert self._started, "call start(requests) first"
        for r in list(self._pending):
            if r.rid == rid and not r.done:
                self._fail_global(r, "cancelled", detail)
                return True
        return any(w.cancel(rid, detail) for w in self.workers)

    def _route(self) -> None:
        """The thin global admission layer: move pending requests,
        strictly in queue order, to the shard ``pick_shard`` selects.
        Stops at the first unroutable head (every shard slot-full) so
        queue order is preserved; after routing, a request's whole
        lifecycle — admission, preemption requeues, swap, failure —
        stays host-local to its shard."""
        cap = [sum(q is None for q in w._slot_req) - len(w._pending)
               for w in self.workers]
        while self._pending:
            w = pick_shard(self.workers, cap)
            if w is None:
                break
            cap[w._shard] -= 1
            r = self._pending.pop(0)
            w._pending.append(r)
            w._all_requests.append(r)

    def _run_prefill_rounds(self) -> None:
        """Advance chunked prefills across shards: each worker stages
        its round-robin chunks host-side (at most its per-shard
        ``prefill_chunks_per_step``), then round ``r`` batches every
        worker's r-th staged chunk into one sharded prefill dispatch —
        workers with nothing left this round ride along as dummy rows.
        Token buffers are padded to the round's largest bucket so all
        shards trace one shape (the compile-count bound stays
        ``len(sc.buckets)``)."""
        S = self.sc.shards
        npp = self.sc.pages_per_seq
        staged = [w._stage_prefill() for w in self.workers]
        for rnd in range(max(len(sp) for sp in staged)):
            preps = [sp[rnd] if rnd < len(sp) else None for sp in staged]
            bucket = max(p[4] for p in preps if p is not None)
            toks = np.zeros((S, bucket), np.int32)
            pos0 = np.zeros((S,), np.int32)
            nval = np.zeros((S,), np.int32)
            rows = np.full((S, npp), GARBAGE_PAGE, np.int32)
            for s, p in enumerate(preps):
                if p is None:
                    continue
                b, _, start, n, pb, ptoks = p
                toks[s, :pb] = ptoks[0]
                pos0[s] = start
                nval[s] = n
                rows[s] = self.workers[s]._btabs.rows[b]
            with TraceAnnotation("engine.prefill", round=rnd,
                                 n=int(nval.sum())):
                last, self._g_cache = self._sharded_prefill(
                    self.params, self.proj, self._g_cache,
                    jnp.asarray(toks), jnp.asarray(pos0),
                    jnp.asarray(nval), jnp.asarray(rows))
                last_np = _to_host(last, "logits")
                self.prefill_chunk_shapes.add(bucket)
                for s, p in enumerate(preps):
                    if p is None:
                        continue
                    b, req, start, n, _, _ = p
                    self.workers[s]._finish_chunk(b, req, start, n, bucket,
                                                  last_np[s: s + 1])

    def _dispatch_decode(self, lives) -> bool:
        """One sharded decode scan over every shard's live slots, then
        per-shard harvest.  Non-live rows export as garbage exactly as
        in the base engine; rows hold shard-local physical page ids.
        Returns whether any slot was freed (same-step refill
        trigger)."""
        sc = self.sc
        rows = np.concatenate(
            [w._btabs.host(live=live)
             for w, live in zip(self.workers, lives)])
        g_live = np.concatenate(lives)
        if self._dynamic_splits:
            pos_np = _to_host(self._g_pos, "pos")
            live_max = int(pos_np[g_live].max()) if g_live.any() else 1
            num_splits = self._splits_for_step(live_max + sc.decode_chunk)
        else:
            num_splits = self._decode_splits
        with TraceAnnotation("engine.dispatch", live=int(g_live.sum())):
            out = self._sharded_decode(
                self.params, self.proj, self._g_cache, self._g_logits,
                self._g_pos, self._g_emitted, self._g_max_new,
                self._g_done, self._g_trunc, self._g_rng,
                jnp.asarray(rows), num_splits=num_splits)
        (self._g_logits, self._g_cache, self._g_pos, self._g_emitted,
         self._g_done, self._g_trunc, self._g_rng, toks, emits) = out
        with TraceAnnotation("engine.harvest"):
            toks_np = _to_host(toks, "toks")
            emits_np = _to_host(emits, "emits")
            freed = False
            for w, live in zip(self.workers, lives):
                if not live.any():
                    continue
                lo, hi = w._base, w._base + w.sc.max_batch
                freed |= w._harvest(live, toks_np[:, lo:hi],
                                    emits_np[:, lo:hi])
        return freed

    def step(self) -> bool:
        """One sharded scheduling iteration, mirroring the base
        ``step`` phase-for-phase with per-shard schedulers: deadlines,
        global routing, per-shard admission, staged prefill rounds
        (one sharded dispatch per round), headroom growth per shard,
        one sharded decode scan, per-shard harvest, same-step refill —
        then sampled audits (per-worker plus the cross-shard
        accounting pass) and the no-progress watchdog over all
        shards."""
        assert self._started, "call start(requests) first"
        sc = self.sc
        self._step_count += 1
        with TraceAnnotation("engine.step", step=self._step_count):
            self._progress_global = False
            for w in self.workers:
                # workers share the parent's scheduler clock so retry
                # backoff, deadlines and chaos schedules line up with the
                # global step count
                w._step_count = self._step_count
                w._progress = False
                w._check_deadlines()
            self._check_global_deadlines()
            self._route()
            for w in self.workers:
                w._admit()
                w.peak_used_pages = max(w.peak_used_pages, w.pool.used_count)
            self._run_prefill_rounds()
            lives = [np.array([w._slot_req[b] is not None
                               and w._prefilled[b] is None
                               for b in range(w.sc.max_batch)])
                     for w in self.workers]
            for w, live in zip(self.workers, lives):
                if live.any():
                    w._ensure_chunk_headroom(live)
                    w.peak_used_pages = max(w.peak_used_pages,
                                            w.pool.used_count)
            if any(live.any() for live in lives):
                if self._dispatch_decode(lives):
                    # refill freed slots in the same step (the base
                    # engine's refill-bubble fix, routed globally)
                    self._route()
                    for w in self.workers:
                        w._admit()
            busy = self._busy()
            if sc.audit and self._step_count % sc.audit_every == 0:
                for w in self.workers:
                    invariants.audit(w)
                invariants.audit_sharded(self)
                self.n_audits += 1
            progress = (self._progress_global
                        or any(w._progress for w in self.workers))
            if busy and not progress:
                self._no_progress += 1
                if (sc.stall_steps
                        and self._no_progress >= sc.stall_steps):
                    raise EngineStalledError(
                        self._no_progress,
                        "\n".join(f"[shard {s}] "
                                  + invariants.scheduler_dump(w)
                                  for s, w in enumerate(self.workers)))
            else:
                self._no_progress = 0
        return busy
