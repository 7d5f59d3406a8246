"""Serving: plan-aware continuous batching with bucketed shapes.

``make_prefill_step`` / ``make_decode_step`` produce the jittable functions
that the dry-run lowers for the ``prefill_*`` and ``decode_*`` / ``long_*``
shape cells. ``ServeEngine`` is the production driver; ``WaveEngine`` is the
fixed-wave baseline it replaced (kept for benchmarking and equivalence
tests — see ``benchmarks/serve_bench.py``).

Serving model (the paper's §5 inference dataflow, engine-level)
---------------------------------------------------------------

The paper keeps ``FFT(w)`` resident in BRAM and streams only activations
through the FFT → ∘ → IFFT pipeline. The engine is the TPU/runtime analogue
of that split, applied at three levels:

* **Frozen frequency weights** — at construction the engine runs
  ``kernels.block_circulant.plan.freeze_params`` ONCE: every circulant table
  is replaced by its rfft ``(wr, wi)`` and the time-domain table is dropped.
  This is the engine's shared plan cache: the same frozen tables (the data
  content of a :class:`~repro.kernels.block_circulant.plan.BCPlan`) are
  threaded as ordinary params into *every* bucketed executable, so no
  prefill/decode trace ever contains an ``rfft(w)`` — exactly one frequency
  transform per weight per engine lifetime (test-enforced via
  ``ops.freq_weights_trace_count``). Tile geometry is likewise derived once
  per layer shape through the lru-cached ``plan_geometry``.

* **Bucketed shapes** — jit recompilation is bounded by rounding every
  launch to a bucket grid: prefill batch sizes come from ``batch_buckets``
  (powers of two up to the slot count; for a prompt bucket only those
  within the runner's ``max_prefill_tokens``, ``prefill_batch_buckets``),
  prompt lengths round up to ``prompt_buckets``, and *decode* launches
  compact the active slots into the smallest ``decode_buckets`` batch that
  holds them. A full engine lifetime therefore compiles at most
  ``len(batch_buckets) · len(prompt_buckets)`` prefill executables plus
  ``len(decode_buckets)`` decode executables (``prewarm()`` compiles them
  all up front). The wave baseline instead recompiles for every distinct
  wave length it happens to see — unbounded in the workload.

* **Decode-side slot compaction** — the paper's throughput argument (and
  CirCNN's, arXiv:1708.08917) is that no FFT → ∘ → IFFT lane ever carries
  dead data. Before each decode launch the engine collects the *active*
  slots' rows, last tokens, and positions into a bucket-shaped launch; the
  runner decodes those rows in place on the slot pool (only each row's new
  cache entries are written) and returns one logits row per lane. In the
  tail of a batch one live request pays for ``pick_bucket(1)`` rows of
  work, not ``batch`` rows (``EngineStats.decode_rows`` /
  ``decode_rows_per_token`` make the saving measurable). Compaction is a
  pure permutation of slot rows — never part of the math — so greedy
  outputs are bit-identical to full-slot decode (``decode_buckets=(batch,)``
  restores the old behavior exactly).

* **Continuous batching, streamed** — requests occupy independent cache
  *slots*; a finished slot admits the next queued request immediately
  instead of stalling the whole wave on the slowest request (the C-LSTM
  pipeline overlap argument, arXiv:1803.06305, applied across sequences).
  Admission order is a :class:`Scheduler` policy (FIFO or
  shortest-prompt-first), and each request carries its own
  :class:`SamplingParams` and stop tokens. The engine serves an open-ended
  stream: ``submit(request)`` returns a request id, ``step()`` advances
  admission + one decode round, ``poll(req_id)`` snapshots progress
  without consuming it, and ``drain()`` runs the loop to idle and claims
  finished outputs. ``generate(list)`` is a thin wrapper over that loop
  (submit all, drain, reorder) — slot state persists across calls instead
  of being reset.

* **Shared-prefix KV reuse** (``prefix_cache=True``) — the CirCNN /
  C-LSTM discipline of touching resident state once, applied across
  requests: prompt heads another request already prefilled are never
  recomputed. Lifecycle of the prefix index:

  1. *match* — admission hashes the new prompt's block-aligned prefixes
     (multiples of ``prefix_block``, longest first) against a host-side
     index of resident slot rows; a hit names a donor slot and a match
     length ``m`` (capped so the tail still produces the first-token
     logits and the tail bucket's pad ring slots stay clear of the copied
     rows: ``m + tail_bucket <= cache_len``);
  2. *copy rows* — the prefill launch gathers the donor's cache rows and
     masks every entry at position ``>= m`` (``pos -> -1``), seeding the
     consumer's rows with exactly the shared head — a device-side row
     copy instead of ``m`` tokens of recomputation
     (``EngineStats.prefill_tokens_saved`` / ``prefix_hits``);
  3. *tail prefill* — only the unmatched tail runs through the model,
     bucket-shaped as usual (reuse composes with prompt buckets), with
     tail positions ``m..L-1`` and pad writes parked on masked ring slots
     past the tail;
  4. *refcount* — a matched donor's rows are pinned (``_slot_refs``)
     until the launch that copies them has run: a pinned free slot is
     never handed to a new request and never borrowed as a decode pad
     lane, so multi-launch admission rounds cannot overwrite rows a
     later launch still reads;
  5. *evict* — eviction is explicit: rows leave the index only when
     their slot is reassigned to a new request, borrowed as a pad lane
     (least-recently-used donors sacrificed first), or the LRU index
     exceeds ``prefix_capacity`` (which forgets entries — rows in slots
     are never freed while referenced).

  Greedy outputs are bit-identical with the prefix cache on or off:
  masked cache entries contribute exactly zero to attention, and the
  copied rows are bit-identical to the rows a full prefill would have
  written (bucket-padding invariance, same params, same positions).

* **Donated decode buffers** (``donate=True``, default) — every
  prefill/decode executable takes the slot cache through
  ``jax.jit(..., donate_argnums)``, so prefill's place-back scatter and
  decode's new entries update the cache in place (XLA input-output
  aliasing) instead of allocating and copying a second full cache per
  step. The engine threads the returned
  cache handle through every call (a donated input buffer is invalid
  after the call), and ``prewarm()`` COMMITS its warm-up results for the
  same reason: discarding them would kill the live cache. Donation never
  changes the math — outputs are bit-identical with it on or off.

Padding correctness: bucketed prefill left-pads prompts and numbers the pad
positions *negatively* (real tokens are always positions ``0..L-1``). The
attention mask drops every key with ``kv_pos < 0``, and pad cache writes
land on ring slots with negative ``pos`` (masked until real tokens overwrite
them), so bucket padding is invisible to the math: greedy outputs are
bit-identical across bucket choices, wave sizes, and the B=1 reference loop.
Recurrent mixers — mamba/rwkv — get a validity mask derived from the same
negative pad positions (``positions >= 0``), so token shifts, conv windows,
and state updates skip pad lanes and bucketed prefill stays bit-identical
to the unbucketed B=1 loop (see ``repro.serve.runner``).

Everything model-shaped sits behind a :class:`~repro.serve.runner.
ModelRunner`: the engine schedules, buckets, indexes prefixes, and
snapshots host state, while the runner owns the per-slot device state tree
and the prefill/decode executables — one engine serves every family in
``configs/`` (attention decoders, rwkv/mamba/jamba hybrids, MoE, enc-dec).

Structural contracts (``repro.analysis``; run via ``ServeEngine.audit()``)
--------------------------------------------------------------------------

Every promise above that is *structural* — visible in the traced program
rather than in its outputs — is gated declaratively by the jaxpr auditor
(``repro.analysis.contracts``), one contract per compiled surface:

* ``serve_prefill[B,S]`` / ``serve_decode[B]`` (one surface per bucketed
  executable): ``NoWeightFFT`` — no fft over parameter-derived data, i.e.
  the freeze-once promise holds in every trace (the ``paper``/``freq``
  impls legitimately stream *activations* through rfft; ``pallas``/``dft``
  additionally promise total ``NoFFT``); ``DenseFallbackDot`` — no
  ``dot_general`` against a circulant layer's dense-equivalent kernel
  (the silent O(n²) fallback); ``NoWeightConcat`` — fused QKV/gate tables
  are pre-concatenated by ``freeze_params``, never stacked per trace.
* ``serve_params``: ``QuantizedTableDtypes`` — frozen tables are int8 with
  f32 per-block scales under ``quantize='int8'``, plain float under
  ``'off'``.
* ``serve_donation[prefill|decode]``: ``DonatedInputsAliased`` — the
  lowered modules really record input-output aliasing for the donated
  cache (donation silently not taking would re-materialize the cache
  every step).
* Cross-engine (CLI-level, ``audit_config``): launch parity — the int8
  engine launches exactly as many Pallas kernels as the fp32 engine
  (in-kernel dequant adds no launch).

``audit()`` returns the violations; ``prewarm(audit=True)`` gates
compilation on them (raises ``StructuralContractError``). CI runs
``python -m repro.analysis --all-configs`` over every registry config.

Failure semantics (the robustness layer; see ``repro.serve.guard``)
-------------------------------------------------------------------

The deployment targets of the paper — FPGAs, mobile/IoT, always-on
streaming (C-LSTM, arXiv:1803.06305) — make preemption, transient device
faults, and overload the normal operating regime. The engine's contract:

* **Terminal states** — every submitted request ends in exactly one of
  ``FINISHED`` (ran to a stop token / ``max_new``), ``FAILED`` (isolated
  error: launch fault or non-finite logits), ``EXPIRED`` (``deadline_ms``
  exceeded), or ``CANCELLED`` (``cancel()`` or load shedding).
  ``poll``/:class:`RequestState` surface the state plus a human-readable
  ``error`` reason; ``drain`` claims the (possibly partial) tokens of any
  terminal request.

* **Deadlines** — a request with ``deadline_ms`` set is expired by a
  step-boundary watchdog (queued or running; the deadline clock starts at
  ``submit``). Expiry recycles the slot immediately: donor refcounts are
  always zero at a step boundary, so the slot returns to the free pool
  with its prefix-index entries intact (a finished/expired slot remains a
  donor until its rows are overwritten).

* **Error isolation** — every prefill/decode launch is wrapped and the
  error classified (``guard.classify_error``): faults raised *before* the
  executable ran leave the donated buffers intact and abort only the
  implicated requests (decode launches retry once — ``transient``);
  anything that may have consumed a donated buffer mid-launch is
  engine-fatal. Non-finite logits are detected by a per-row finiteness
  flag folded into the existing prefill/decode executables (no new
  compiles — the compile budget is unchanged, test-enforced): only the
  poisoned row's request is ``FAILED``, its slot rows are scrubbed back
  to blank (a masked NaN still contaminates attention through ``0·NaN``),
  and the rest of the batch continues bit-identically.

* **Load shedding** — ``max_queue`` bounds admission; ``shed_policy``
  picks between rejecting new work (``QueueFullError`` backpressure — the
  request is never enqueued) and ``drop-oldest`` (the longest-queued
  request is ``CANCELLED`` to make room). ``generate`` absorbs
  backpressure internally (step-and-retry); streaming callers handle
  ``QueueFullError`` themselves. ``EngineStats`` counts ``rejected``,
  ``aborted``, ``expired``, ``cancelled``, ``recoveries``.

* **Snapshot/restore** — ``snapshot()`` serializes the complete serving
  state (slot table, scheduler queue, per-request outputs and RNG states,
  prefix index, KV cache) through ``ft.checkpoint``'s atomic machinery;
  ``snapshot_every`` automates it at step boundaries (skipping an EMPTY
  engine — a snapshot with nothing to resume is never written, and
  ``restore()`` refuses one with an actionable error). After an
  engine-fatal error (``EngineFatalError`` — the engine refuses further
  work), a *replacement* engine with the same configuration calls
  ``restore()`` and resumes every in-flight decode mid-stream; decoding
  is deterministic (greedy argmax or counter-free per-request RNG whose
  state is captured), so outputs are bit-identical to an uninterrupted
  run (test-enforced).

* **Tenancy** — every :class:`Request` bills to a ``tenant``; the
  scheduler's ``fair`` policy keeps one FIFO queue per tenant and admits
  by weighted deficit-round-robin (``tenant_weights``), so a bursty
  tenant cannot starve the others: each backlogged tenant admits at
  least one request per rotation and in the long run admissions track
  the weights (±1 request per round, bench-enforced). ``EngineStats``
  carries per-tenant counters (submitted/admitted/completed/rejected/
  expired/cancelled/aborted/tokens) and a per-tenant TTFT histogram;
  the fault injector's audit log names the tenants riding each launch.
  Per-request outputs are tenant-independent — fairness reorders
  admission, never the math.

* **SLO instrumentation** — ``EngineStats.ttft_ms`` (submit → first
  token) is a streaming :class:`LatencyHistogram` over fixed log-spaced
  buckets: p50/p99 read in O(buckets), memory is constant, and
  ``snapshot()`` serializes the bucket counts exactly — a restored engine
  reports the same quantiles. Inter-token gaps are the client's to time.
  The engine's phases are ``jax.profiler.TraceAnnotation`` spans
  (``serve.step``, ``serve.admit``, ``serve.prefill.*``,
  ``serve.decode.*``), recorded only while a profiler session runs.
  The async front-end (``repro.serve.frontend``) maps tenants to SLO
  *classes* (interactive/standard/batch) that default ``deadline_ms``
  and DRR weights, and enforces per-tenant token-bucket admission
  upstream of the queue bound. ``QueueFullError`` carries
  ``retry_after_hint`` (queue depth over the observed drain rate) so
  shed callers back off proportionally instead of spinning.

* **Self-healing** — ``repro.serve.supervisor.Supervisor`` owns the
  engine lifecycle: it catches ``EngineFatalError`` mid-step, builds a
  replacement engine from its factory, restores the latest snapshot,
  re-submits in-flight work that post-dates the snapshot (rid-remapped),
  and de-duplicates token emission against per-request high-water marks
  so every stream is delivered at-most-once — zero duplicated and zero
  lost tokens across a heal (chaos-tested). With a
  ``repro.serve.prefix_store.PrefixStore`` attached, evicted prefix
  donors spill to host memory and a replacement engine *adopts* the
  hottest entries back into free slots, warm-starting on hot prompt
  heads instead of cold-prefilling them.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import json
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.ft.checkpoint import (latest_step as ckpt_latest_step,
                                 restore_checkpoint, save_checkpoint)
from repro.ft.driver import StragglerWatchdog
from repro.serve.guard import (CANCELLED, EXPIRED, FAILED, FINISHED, QUEUED,
                               RUNNING, TERMINAL_STATES, EngineFatalError,
                               QueueFullError, classify_error,
                               flatten_state_tree, unflatten_state_tree)
from repro.serve.runner import make_runner, recurrent_mixer_names

__all__ = [
    "make_prefill_step",
    "make_decode_step",
    "SamplingParams",
    "Request",
    "RequestState",
    "Scheduler",
    "LatencyHistogram",
    "TenantStats",
    "EngineStats",
    "ServeEngine",
    "WaveEngine",
    "pow2_buckets",
    "pick_bucket",
    "batch_split",
    "validate_buckets",
]


# ---------------------------------------------------------------------------
# Jittable step builders (also used by launch.dryrun)
# ---------------------------------------------------------------------------


def make_prefill_step(model, cfg: ModelConfig):
    def prefill_step(params, tokens, cache, extra=None, positions=None):
        """tokens (B, S) -> (last logits (B, V), filled cache).

        ``positions`` (B, S) overrides the default ``0..S-1`` numbering. The
        bucketed engines pass left-padded rows whose pad positions are
        *negative*, so padding is masked out of attention (``kv_pos < 0``)
        and out of the cache instead of leaking into the output.
        """
        kwargs = {}
        if cfg.family == "vlm" and extra is not None:
            kwargs["img_embeds"] = extra
        if cfg.family == "encdec":
            logits, new_cache, _ = model.forward(
                params, extra, tokens, cache=cache, logits_mode="last"
            )
            return logits[:, -1], new_cache
        logits, new_cache, _ = model.forward(
            params, tokens, cache=cache, logits_mode="last",
            positions=positions, **kwargs
        )
        return logits[:, -1], new_cache

    return prefill_step


def make_decode_step(model, cfg: ModelConfig):
    def decode_step(params, tokens, cache, pos):
        """tokens (B, 1), pos (B,) -> (logits (B, V), cache)."""
        return model.decode_step(params, tokens, cache, pos)

    return decode_step


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    """Powers of two from ``lo``, always terminated by ``hi`` itself."""
    if hi < 1:
        raise ValueError(f"bucket upper bound must be >= 1, got {hi}")
    out = []
    b = max(1, int(lo))
    while b < hi:
        out.append(b)
        b *= 2
    out.append(int(hi))
    return tuple(sorted(set(out)))


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {max(buckets)}")


def batch_split(m: int, buckets: Sequence[int]) -> List[int]:
    """Greedy decomposition of ``m`` into bucket-sized chunks, largest first.

    ``buckets`` must contain 1 so every m decomposes exactly (the engine's
    batch buckets always do); a list that cannot cover the remainder raises
    ``ValueError`` naming the offending buckets.
    """
    desc = sorted(set(int(b) for b in buckets), reverse=True)
    out: List[int] = []
    rem = int(m)
    while rem > 0:
        b = next((b for b in desc if b <= rem), None)
        if b is None:
            raise ValueError(
                f"batch buckets {sorted(desc)} cannot decompose {m}: no "
                f"bucket <= remainder {rem} (include 1 in the bucket list)"
            )
        out.append(b)
        rem -= b
    return out


def validate_buckets(name: str, buckets: Sequence[int], hi: int,
                     *, require_hi: bool = True) -> Tuple[int, ...]:
    """Normalize a user-supplied bucket list: sorted unique ints in
    ``[1, hi]``, with ``hi`` itself appended when ``require_hi`` so every
    admissible size maps to a bucket. Raises ``ValueError`` naming the
    bucket list otherwise (construction-time — never mid-serving)."""
    try:
        bk = tuple(sorted(set(int(b) for b in buckets)))
    except (TypeError, ValueError):
        raise ValueError(f"{name} must be a sequence of ints; got {buckets!r}")
    if not bk or bk[0] < 1 or bk[-1] > hi:
        raise ValueError(f"{name} must lie in [1, {hi}]; got {bk}")
    if require_hi and bk[-1] != hi:
        bk = bk + (hi,)
    return bk


# ---------------------------------------------------------------------------
# Requests, sampling, scheduling
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling. ``temperature <= 0`` means greedy argmax."""

    temperature: float = 0.0
    top_k: int = 0          # 0 = full vocab
    seed: int = 0

    def make_rng(self) -> np.random.Generator:
        return np.random.default_rng(self.seed)


def _sample_token(logits: np.ndarray, sp: SamplingParams,
                  rng: np.random.Generator) -> int:
    if sp.temperature <= 0.0:
        return int(np.argmax(logits))
    z = logits.astype(np.float64) / float(sp.temperature)
    vocab = z.shape[-1]
    # top_k == 0 or top_k >= vocab both mean the full vocabulary survives
    if 0 < sp.top_k < vocab:
        # exactly top_k candidates, ties at the k-th value broken
        # deterministically toward the lower token id (a `z >= kth` mask
        # would keep every tied candidate — more than top_k survivors).
        # O(V): everything strictly above the k-th value survives, then the
        # lowest-id threshold ties fill the remaining seats (nonzero
        # returns ascending indices).
        kth = np.partition(z, -sp.top_k)[-sp.top_k]
        above = np.nonzero(z > kth)[0]
        ties = np.nonzero(z == kth)[0]
        keep = np.concatenate([above, ties[: sp.top_k - above.size]])
        masked = np.full_like(z, -np.inf)
        masked[keep] = z[keep]
        z = masked
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(p.shape[-1], p=p))


@dataclasses.dataclass
class Request:
    """``deadline_ms``: wall-clock TTL measured from ``submit`` — the
    step-boundary watchdog EXPIREs the request (queued or running) once it
    elapses. ``None`` means no deadline.

    ``extra``: per-request conditioning for families whose runner declares
    ``requires_extra`` — for enc-dec configs, the encoder frame embeddings
    with shape ``(enc_seq, d_model)``. Decoder-only families must leave it
    ``None`` (the runner's ``validate_request`` enforces both ways).

    ``tenant``: the tenant the request bills to. Under the scheduler's
    ``fair`` policy it keys the per-tenant DRR queue; per-tenant counters
    and TTFT histograms in :class:`EngineStats` key on it under every
    policy. The async front-end derives ``deadline_ms`` defaults and
    token-bucket admission from the tenant's SLO class."""

    prompt: np.ndarray
    max_new: int = 16
    stop_tokens: Tuple[int, ...] = ()
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    deadline_ms: Optional[float] = None
    extra: Optional[np.ndarray] = None
    tenant: str = "default"

    def __post_init__(self):
        # accept any iterable of token ids but store a tuple, so equality,
        # hashing of the field, and `tok in stop_tokens` behave uniformly
        self.stop_tokens = tuple(int(t) for t in self.stop_tokens)
        self.tenant = str(self.tenant)
        if not self.tenant:
            raise ValueError("tenant must be a non-empty string")

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).reshape(-1).shape[0])


@dataclasses.dataclass(frozen=True)
class RequestState:
    """``poll`` snapshot: tokens so far, terminal flag, lifecycle
    ``status`` (``QUEUED``/``RUNNING``/``FINISHED``/``FAILED``/``EXPIRED``/
    ``CANCELLED``) and, for failed terminals, the ``error`` reason.
    ``done`` is True exactly when ``status`` is terminal (``FINISHED`` is
    the only *successful* terminal)."""

    req_id: int
    done: bool
    tokens: Tuple[int, ...]
    status: str = QUEUED
    error: Optional[str] = None


def _validate_request(r: Request, cache_len: int) -> None:
    """Shared admission contract: no silent truncation, no zero budgets."""
    L = r.prompt_len
    if L == 0:
        raise ValueError("empty prompt")
    if r.max_new < 1:
        raise ValueError(f"max_new must be >= 1, got {r.max_new}")
    if r.deadline_ms is not None and r.deadline_ms <= 0:
        raise ValueError(
            f"deadline_ms must be > 0 (or None for no deadline), "
            f"got {r.deadline_ms}")
    if L > cache_len:
        raise ValueError(
            f"prompt length {L} exceeds cache_len={cache_len}: the KV cache "
            f"cannot hold the prompt (raise cache_len or truncate the prompt)"
        )
    # positions written: prompt 0..L-1, then decoded tokens L..L+max_new-2
    # (the final generated token is returned but never fed back)
    if L + r.max_new - 1 > cache_len:
        raise ValueError(
            f"prompt length {L} + max_new={r.max_new} needs "
            f"{L + r.max_new - 1} cache positions but cache_len={cache_len}: "
            f"the ring cache would silently overwrite live context "
            f"(raise cache_len or lower max_new)"
        )


class Scheduler:
    """Admission queue: ``fifo``, ``sjf`` (shortest-prompt-first), or
    ``fair`` (weighted deficit-round-robin across tenants).

    SJF groups short prompts into the same admission round, which tends to
    land them in one prefill bucket (fewer, fuller launches); FIFO preserves
    arrival order. ``fair`` keeps one FIFO queue per ``Request.tenant`` and
    admits by deficit-round-robin: each rotation visit grants a tenant its
    ``tenant_weights`` quantum (default 1), so a backlogged tenant admits
    requests proportional to its weight and no tenant starves — every
    backlogged tenant receives at least one admission per full rotation.
    Per-request outputs are identical under every policy — slots are
    independent — only throughput/latency ordering changes.

    ``max_queue`` bounds the queue depth (load shedding): a ``submit`` at
    the bound either raises :class:`QueueFullError` (``shed_policy
    "reject"`` — backpressure, the item is NOT enqueued; carries the
    engine's ``retry_after_hint`` when a ``retry_hint`` callable is wired)
    or sheds the longest-queued item to make room (``"drop-oldest"``,
    returned to the caller to finalize). ``None`` (default) keeps the
    queue unbounded.

    Internals: live items sit in ``_entries`` (seq -> entry); the policy
    heap (fifo/sjf), the per-tenant deques (fair), and the arrival-order
    heap that serves ``drop_oldest`` all hold *seqs* and delete lazily —
    dead seqs are skipped when popped. ``drop_oldest`` is therefore
    O(log n) amortized (one lazy heap pop) instead of the old O(n) scan +
    ``heapify`` per shed, which made sustained overload quadratic.
    """

    POLICIES = ("fifo", "sjf", "fair")
    SHED_POLICIES = ("reject", "drop-oldest")

    def __init__(self, policy: str = "fifo",
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject",
                 tenant_weights: Optional[Dict[str, int]] = None,
                 retry_hint=None):
        if policy not in self.POLICIES:
            raise ValueError(
                f"unknown scheduler policy {policy!r}; one of {self.POLICIES}"
            )
        if shed_policy not in self.SHED_POLICIES:
            raise ValueError(
                f"unknown shed policy {shed_policy!r}; one of "
                f"{self.SHED_POLICIES}"
            )
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError(f"max_queue must be >= 1 (or None for "
                             f"unbounded), got {max_queue}")
        if tenant_weights:
            if policy != "fair":
                raise ValueError(
                    f"tenant_weights only apply to the 'fair' policy "
                    f"(got policy={policy!r})")
            for t, w in tenant_weights.items():
                if int(w) < 1:
                    raise ValueError(
                        f"tenant weight must be >= 1; got {t!r}: {w}")
        self.policy = policy
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_policy = shed_policy
        self.tenant_weights = {str(t): int(w)
                               for t, w in (tenant_weights or {}).items()}
        self.retry_hint = retry_hint     # zero-arg callable -> seconds|None
        # seq -> (key, item, tenant, prompt_len); insertion order == queue
        # identity for serialization (sorted by seq)
        self._entries: Dict[int, Tuple[int, object, str, int]] = {}
        self._order: list = []           # lazy heap of (key, seq) [fifo/sjf]
        self._arrival: list = []         # lazy min-heap of seq [drop_oldest]
        self._tq: Dict[str, object] = {}  # tenant -> deque of seq [fair]
        self._deficit: Dict[str, float] = {}
        self._rr: List[str] = []         # tenant rotation, first-seen order
        self._rr_pos = 0
        self._seq = 0
        self._front = 0

    def _key(self, prompt_len: int) -> int:
        return prompt_len if self.policy == "sjf" else 0

    def _insert(self, seq: int, key: int, item, tenant: str,
                prompt_len: int, *, front: bool = False) -> None:
        self._entries[seq] = (key, item, tenant, prompt_len)
        heapq.heappush(self._arrival, seq)
        if self.policy == "fair":
            q = self._tq.get(tenant)
            if q is None:
                q = self._tq[tenant] = deque()
                self._deficit.setdefault(tenant, 0.0)
                self._rr.append(tenant)
            (q.appendleft if front else q.append)(seq)
        else:
            heapq.heappush(self._order, (key, seq))

    def submit(self, item, prompt_len: int, tenant: str = "default"):
        """Enqueue; returns the item shed to make room (``drop-oldest`` at
        the bound) or None. Raises :class:`QueueFullError` at the bound
        under ``reject``."""
        dropped = None
        if self.max_queue is not None \
                and len(self._entries) >= self.max_queue:
            if self.shed_policy == "reject":
                hint = self.retry_hint() if self.retry_hint else None
                raise QueueFullError(len(self._entries), self.max_queue,
                                     retry_after_hint=hint)
            dropped = self.drop_oldest()
        self._insert(self._seq, self._key(prompt_len), item, str(tenant),
                     prompt_len)
        self._seq += 1
        return dropped

    def drop_oldest(self):
        """Remove and return the longest-queued item (smallest sequence
        number — arrival order, regardless of policy). O(log n) amortized:
        one lazy pop from the arrival heap; the policy-side reference dies
        lazily."""
        while self._arrival:
            seq = heapq.heappop(self._arrival)
            e = self._entries.pop(seq, None)
            if e is not None:
                return e[1]
        raise IndexError("drop_oldest on an empty queue")

    def purge(self, keep) -> int:
        """Drop every queued item for which ``keep(item)`` is false
        (stale entries: requests cancelled/expired while queued). Returns
        the number dropped. Heap/deque references die lazily."""
        dead = [seq for seq, e in self._entries.items() if not keep(e[1])]
        for seq in dead:
            del self._entries[seq]
        return len(dead)

    def put_front(self, item, prompt_len: int,
                  tenant: str = "default") -> None:
        """Re-enqueue ahead of every same-key item (deferred admissions:
        a request bumped out of a round goes back to the head of the line,
        not the tail). Under ``fair`` the item returns to the head of its
        tenant's queue (its DRR quantum was already charged when first
        taken)."""
        self._front -= 1
        self._insert(self._front, self._key(prompt_len), item, str(tenant),
                     prompt_len, front=True)

    def _take_ordered(self, n: int) -> list:
        out = []
        while self._order and len(out) < n:
            _, seq = heapq.heappop(self._order)
            e = self._entries.pop(seq, None)
            if e is not None:
                out.append(e[1])
        return out

    def _take_fair(self, n: int) -> list:
        out = []
        while self._entries and len(out) < n:
            t = self._rr[self._rr_pos % len(self._rr)]
            self._rr_pos = (self._rr_pos + 1) % len(self._rr)
            q = self._tq[t]
            while q and q[0] not in self._entries:
                q.popleft()              # lazy-deleted (purged/shed) seqs
            if not q:
                # an idle tenant banks no deficit: credit accrues only
                # while backlogged, so a returning tenant cannot burst
                # past its weight
                self._deficit[t] = 0.0
                continue
            self._deficit[t] += float(self.tenant_weights.get(t, 1))
            while q and len(out) < n and self._deficit[t] >= 1.0:
                seq = q.popleft()
                e = self._entries.pop(seq, None)
                if e is None:
                    continue
                out.append(e[1])
                self._deficit[t] -= 1.0
            while q and q[0] not in self._entries:
                q.popleft()
            if not q:
                self._deficit[t] = 0.0
        return out

    def take(self, n: int) -> list:
        if self.policy == "fair":
            return self._take_fair(n)
        return self._take_ordered(n)

    def __len__(self) -> int:
        return len(self._entries)

    # -- serialization (engine snapshot/restore) ----------------------------
    def state_dict(self) -> Dict[str, object]:
        """Everything needed to rebuild the queue bit-identically: live
        entries (sorted by seq — negative front-pushed seqs order ahead of
        arrivals, most recent first, matching deque/heap pop order) plus
        the DRR rotation state. Items must be JSON-serializable (the
        engine queues int rids)."""
        return {
            "entries": [[int(seq), int(e[0]), e[1], e[2], int(e[3])]
                        for seq, e in sorted(self._entries.items())],
            "seq": int(self._seq),
            "front": int(self._front),
            "deficit": [[t, float(d)]
                        for t, d in sorted(self._deficit.items())],
            "rr": list(self._rr),
            "rr_pos": int(self._rr_pos),
        }

    def load_state(self, d: Dict[str, object]) -> None:
        """Inverse of :meth:`state_dict` into a fresh scheduler."""
        if self._entries:
            raise RuntimeError("load_state needs an empty scheduler")
        self._seq = int(d["seq"])
        self._front = int(d["front"])
        # seed the rotation before re-inserting so first-seen order (and
        # therefore the DRR visit order) survives even for tenants whose
        # entries were all consumed
        for t in d.get("rr", []):
            if self.policy == "fair" and t not in self._tq:
                self._tq[t] = deque()
                self._deficit.setdefault(t, 0.0)
                self._rr.append(t)
        for seq, key, item, tenant, plen in d["entries"]:
            self._insert(int(seq), int(key), item, str(tenant), int(plen))
        for t, dv in d.get("deficit", []):
            if t in self._deficit or self.policy != "fair":
                self._deficit[t] = float(dv)
        self._rr_pos = int(d.get("rr_pos", 0))


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


class LatencyHistogram:
    """Streaming latency histogram over FIXED log-spaced millisecond
    buckets (1-2-5 series, 10µs..100s, plus overflow), so p50/p99 are
    O(buckets) to read, memory is constant regardless of traffic, and
    ``snapshot()`` serializes the counts exactly (restore resumes the same
    distribution — no reservoir to resample). Quantiles return the upper
    bound of the covering bucket: an upper estimate, bounded-error by the
    bucket spacing (≤ 2.5× the true value), which is what an SLO check
    needs — a reported p99 under the target guarantees the true p99 is."""

    BOUNDS_MS: Tuple[float, ...] = tuple(
        m * (10.0 ** e) for e in range(-2, 5) for m in (1.0, 2.0, 5.0)
    ) + (1e5,)

    def __init__(self, counts: Optional[Sequence[int]] = None):
        n = len(self.BOUNDS_MS) + 1          # + overflow bucket
        if counts is None:
            self.counts = [0] * n
        else:
            if len(counts) != n:
                raise ValueError(
                    f"LatencyHistogram needs {n} bucket counts, "
                    f"got {len(counts)} — snapshot from a different "
                    f"bucket layout")
            self.counts = [int(c) for c in counts]

    @property
    def count(self) -> int:
        return sum(self.counts)

    def observe(self, ms: float) -> None:
        self.counts[bisect.bisect_left(self.BOUNDS_MS, float(ms))] += 1

    def quantile(self, q: float) -> Optional[float]:
        """Upper bound of the bucket containing the q-quantile (``None``
        on an empty histogram; ``inf`` when it falls in overflow)."""
        total = self.count
        if total == 0:
            return None
        target = q * total
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                return (self.BOUNDS_MS[i] if i < len(self.BOUNDS_MS)
                        else float("inf"))
        return float("inf")

    @property
    def p50(self) -> Optional[float]:
        return self.quantile(0.50)

    @property
    def p99(self) -> Optional[float]:
        return self.quantile(0.99)

    def as_dict(self) -> Dict[str, object]:
        return {"count": self.count, "p50_ms": self.p50, "p99_ms": self.p99}


@dataclasses.dataclass
class TenantStats:
    """Per-tenant slice of the engine counters plus a TTFT histogram —
    the fairness/SLO evidence (``serve_bench --workload tenants`` asserts
    completed-request shares against the DRR weights from these)."""

    submitted: int = 0
    admitted: int = 0                      # taken from the queue into a slot
    completed: int = 0
    rejected: int = 0
    expired: int = 0
    cancelled: int = 0
    aborted: int = 0
    tokens: int = 0
    ttft_ms: LatencyHistogram = dataclasses.field(
        default_factory=LatencyHistogram)

    def as_dict(self) -> Dict[str, object]:
        return {
            "submitted": self.submitted, "admitted": self.admitted,
            "completed": self.completed, "rejected": self.rejected,
            "expired": self.expired, "cancelled": self.cancelled,
            "aborted": self.aborted, "tokens": self.tokens,
            "ttft": self.ttft_ms.as_dict(),
        }


@dataclasses.dataclass
class EngineStats:
    """Lifetime counters (never reset by ``generate``; compile bounds are
    engine-lifetime properties)."""

    prefill_calls: int = 0
    decode_steps: int = 0
    tokens_generated: int = 0
    requests_completed: int = 0
    padded_prompt_tokens: int = 0          # bucket-padding waste
    slot_steps_active: int = 0             # Σ over decode steps of active slots
    decode_rows: int = 0                   # Σ over decode steps of rows launched
    decode_ctx_positions: int = 0          # Σ over launched rows of filled positions
    prefix_lookups: int = 0                # admissions probed against the index
    prefix_hits: int = 0                   # admissions seeded from a donor
    prefill_tokens_saved: int = 0          # Σ matched prefix tokens never rerun
    rejected: int = 0                      # load-shed submissions (both policies)
    aborted: int = 0                       # FAILED terminals (isolated errors)
    expired: int = 0                       # EXPIRED terminals (deadline_ms)
    cancelled: int = 0                     # CANCELLED terminals (cancel/shed)
    recoveries: int = 0                    # successful restore() calls
    snapshots: int = 0                     # snapshot() calls
    launch_retries: int = 0                # transient decode launches retried
    slow_steps: int = 0                    # straggler-watchdog flagged steps
    prefix_spills: int = 0                 # evicted donors spilled to store
    prefix_adoptions: int = 0              # store entries adopted into slots
    prefill_shapes: Set[Tuple[int, int]] = dataclasses.field(
        default_factory=set)
    decode_shapes: Set[int] = dataclasses.field(default_factory=set)
    # SLO instrumentation: streaming p50/p99 over fixed buckets, so the
    # histograms serialize exactly through snapshot()/restore()
    ttft_ms: LatencyHistogram = dataclasses.field(
        default_factory=LatencyHistogram)      # submit -> first token
    tenants: Dict[str, TenantStats] = dataclasses.field(
        default_factory=dict)

    def tenant(self, name: str) -> TenantStats:
        """Get-or-create the per-tenant slice."""
        ts = self.tenants.get(name)
        if ts is None:
            ts = self.tenants[name] = TenantStats()
        return ts

    @property
    def tokens_per_decode_step(self) -> float:
        """Mean decoded tokens per decode launch — the batching-efficiency
        signal that carries to hardware (wave stalls push it toward 1·)."""
        if self.decode_steps == 0:
            return 0.0
        return self.slot_steps_active / self.decode_steps

    @property
    def decode_rows_per_token(self) -> float:
        """Mean FFT → ∘ → IFFT rows launched per generated token — the
        decode-side work amplification. Full-slot decode pays ``batch`` rows
        per step regardless of occupancy; slot compaction pays the bucket
        that holds the active set, so tail-heavy workloads pull this toward
        1.0. (Prefill-produced first tokens cost no decode rows, so a
        perfectly compacted engine can sit slightly below 1.)"""
        if self.tokens_generated == 0:
            return 0.0
        return self.decode_rows / self.tokens_generated

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefix-index probes that found a usable donor."""
        if self.prefix_lookups == 0:
            return 0.0
        return self.prefix_hits / self.prefix_lookups

    def as_dict(self) -> Dict[str, object]:
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)
             if f.name not in ("prefill_shapes", "decode_shapes",
                               "ttft_ms", "tenants")}
        d["prefill_shapes"] = sorted(self.prefill_shapes)
        d["decode_shapes"] = sorted(self.decode_shapes)
        d["tokens_per_decode_step"] = self.tokens_per_decode_step
        d["decode_rows_per_token"] = self.decode_rows_per_token
        d["prefix_hit_rate"] = self.prefix_hit_rate
        d["ttft"] = self.ttft_ms.as_dict()
        d["tenants"] = {t: ts.as_dict()
                        for t, ts in sorted(self.tenants.items())}
        return d


# ---------------------------------------------------------------------------
# The continuous-batching engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous batching over ``batch`` cache slots with bucketed shapes.

    * admission is per-slot: a finished slot immediately accepts the next
      queued request (``Scheduler`` policy), instead of the whole batch
      waiting for its slowest member;
    * prefill launches are rounded to ``(batch_bucket, prompt_bucket)``
      shapes so the engine compiles at most ``max_prefill_variants``
      prefill executables;
    * decode launches compact the active slots into the smallest
      ``decode_buckets`` batch that holds them (the runner decodes those
      rows in place on the slot pool), so the engine compiles at most
      ``len(decode_buckets)`` decode executables and the tail of a batch
      never pays full-slot row work;
    * frozen frequency weights are computed exactly once at construction
      (``freeze_params``) and shared by every bucketed executable — the
      paper's BRAM-resident FFT(w), with the jitted steps containing no
      ``rfft(w)`` (fused QKV groups additionally read one pre-concatenated
      stacked table — no weight concatenate in any trace);
    * ``prefix_cache=True`` reuses resident KV rows across requests that
      share a prompt head: admission copies the matched rows from a donor
      slot and prefills only the tail (see the module docstring for the
      match → copy → tail-prefill → refcount → evict lifecycle);
    * ``donate=True`` (default) donates the cache into every executable so
      prefill's place-back scatter and decode's new entries update HBM in
      place — no per-step full-cache copy; all callers thread the returned
      handle.

    Streaming API: ``submit(request) -> req_id`` enqueues, ``step()``
    advances admission plus one decode round, ``poll(req_id)`` snapshots
    progress (:class:`RequestState`) without consuming it, and
    ``drain(req_ids=None)`` runs to idle and claims finished outputs.
    ``generate`` is a thin wrapper (submit all → drain → reorder): a list
    of :class:`Request` in, per-request token lists out in request order.
    Greedy outputs are bit-identical to the B=1 one-request-at-a-time loop,
    to :class:`WaveEngine`, and across ``decode_buckets`` choices — bucket
    padding is attention-masked and slot compaction is a pure permutation,
    never part of the math.

    **ModelRunner contract.** Everything model-shaped sits behind
    ``self.runner`` (:mod:`repro.serve.runner`); the engine holds no model
    reference and composes exactly six runner operations: ``init_state`` /
    ``prefill`` / ``decode`` / ``gather_state`` / ``place_state`` /
    ``reset_rows``.

    * *Pad semantics* — prefill buckets are LEFT-padded with negative pad
      positions; the runner must make pad lanes contribute exactly nothing
      (attention masks ``kv_pos < 0``; recurrent mixers consume a
      ``positions >= 0`` validity mask), so the same request produces
      bit-identical tokens at every bucket shape, including the
      unbucketed B=1 loop.
    * *State-tree shape rules* — the slot state is an arbitrary pytree of
      arrays with one row per slot per leaf; only the runner knows which
      axis is the slot axis (axis 0 for plain decoder groups, axis 1 for
      repeat-stacked groups and enc-dec layer stacks). The engine treats
      the tree as opaque: snapshot/restore flattens leaves generically
      (``guard.flatten_state_tree``) and rebuilds against
      ``init_state``'s structure and dtypes.
    * *Capability flags* — ``supports_prefix_cache`` declares whether
      state rows are position-sliceable; requesting ``prefix_cache=True``
      against a runner without it raises the runner's actionable
      ``prefix_cache_unsupported_reason`` at construction, and the
      prefix index/matcher stay inert regardless. ``min_cache_len``
      bounds ``cache_len`` from below. ``requires_extra`` marks families
      whose requests carry per-request conditioning (``Request.extra`` —
      enc-dec encoder frames), batched into every prefill launch and
      synthesized by ``runner.prewarm_extra`` for warm-up.
    """

    def __init__(self, model, cfg: ModelConfig, params, batch: int,
                 cache_len: int, *,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 decode_buckets: Optional[Sequence[int]] = None,
                 policy: str = "fifo",
                 prefix_cache: bool = False,
                 prefix_block: int = 8,
                 prefix_capacity: int = 256,
                 donate: bool = True,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject",
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0,
                 fault_injector=None,
                 clock=time.monotonic,
                 quantize: str = "off",
                 tenant_weights: Optional[Dict[str, int]] = None,
                 prefix_store=None):
        # fail fast on unknown policies / bad bounds (before param freeze)
        Scheduler(policy, max_queue=max_queue, shed_policy=shed_policy,
                  tenant_weights=tenant_weights)
        if int(snapshot_every) < 0:
            raise ValueError(
                f"snapshot_every must be >= 0, got {snapshot_every}")
        if int(snapshot_every) > 0 and snapshot_dir is None:
            raise ValueError("snapshot_every needs snapshot_dir")
        from repro.kernels.block_circulant.plan import (_check_quantize,
                                                        freeze_params)
        _check_quantize(quantize)
        if quantize != "off" and not cfg.swm.enabled:
            raise ValueError(
                "quantize applies to frozen circulant tables; this config "
                "has swm disabled")
        self.batch, self.cache_len = int(batch), int(cache_len)
        # the runner is the ONLY model surface the engine touches from here
        self.runner = make_runner(model, cfg, self.cache_len)
        if self.cache_len < self.runner.min_cache_len:
            raise ValueError(
                f"cache_len={self.cache_len} is below "
                f"{type(self.runner).__name__}'s minimum of "
                f"{self.runner.min_cache_len}")
        if cfg.swm.enabled:
            params = freeze_params(self.runner.specs(), params,
                                   quantize=quantize)
        self.quantize = quantize
        self.cfg, self.params = cfg, params
        self.policy = policy
        self.prefix_cache = bool(prefix_cache)
        self.prefix_block = int(prefix_block)
        self.prefix_capacity = int(prefix_capacity)
        if self.prefix_cache:
            if self.prefix_block < 1:
                raise ValueError(
                    f"prefix_block must be >= 1, got {prefix_block}")
            if self.prefix_capacity < 1:
                raise ValueError(
                    f"prefix_capacity must be >= 1, got {prefix_capacity}")
            if not self.runner.supports_prefix_cache:
                raise ValueError(
                    f"prefix_cache=True is unsupported for "
                    f"{type(self.runner).__name__}: "
                    f"{self.runner.prefix_cache_unsupported_reason}")
        if prefix_store is not None and not self.prefix_cache:
            raise ValueError(
                "prefix_store needs prefix_cache=True: the store spills "
                "and adopts prefix-index donor rows, which only exist "
                "with the prefix cache on")
        self.donate = bool(donate)
        if prompt_buckets is None:
            prompt_buckets = pow2_buckets(min(8, self.cache_len),
                                          self.cache_len)
        # every admissible prompt must fit -> cache_len always terminates
        self.prompt_buckets = validate_buckets(
            "prompt_buckets", prompt_buckets, self.cache_len)
        self.batch_buckets = pow2_buckets(1, self.batch)
        cap = self.runner.max_prefill_tokens
        self.prefill_batch_buckets = {
            Sb: [b for b in self.batch_buckets
                 if not cap or b * Sb <= max(cap, Sb)]
            for Sb in self.prompt_buckets}
        if decode_buckets is None:
            decode_buckets = self.batch_buckets
        # any active-slot count must map to a bucket -> batch terminates
        self.decode_buckets = validate_buckets(
            "decode_buckets", decode_buckets, self.batch)
        self.stats = EngineStats()
        # raw (unjitted) fns kept for jaxpr introspection in tests
        self._prefill_fn = self.runner.prefill
        self._decode_fn = self.runner.decode
        # donating the cache argument lets XLA alias input and output slot
        # caches: prefill's place-back scatter and decode's new entries
        # update HBM in place instead of writing a second full cache per
        # launch. Every caller threads the returned handle (the donated
        # input is dead after the call).
        if self.donate:
            self._prefill = jax.jit(self._prefill_fn, donate_argnums=(3,))
            self._decode = jax.jit(self._decode_fn, donate_argnums=(2,))
        else:
            self._prefill = jax.jit(self._prefill_fn)
            self._decode = jax.jit(self._decode_fn)
        # robustness knobs: bounded admission, fault injection hooks,
        # injectable clock (deadlines/watchdog), snapshot policy
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_policy = shed_policy
        self.tenant_weights = {str(t): int(w)
                               for t, w in (tenant_weights or {}).items()}
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = int(snapshot_every)
        self.faults = fault_injector
        self.prefix_store = prefix_store
        self._clock_fn = clock
        self._watchdog = StragglerWatchdog()
        self._fatal: Optional[str] = None
        self._step_count = 0
        # drain-rate estimate (terminals/sec EWMA) backing QueueFullError's
        # retry_after_hint; per-rid submit times feed the TTFT histogram
        self._drain_rate = 0.0
        self._prev_step_t: Optional[float] = None
        self._prev_terminals = 0
        self._terminals = 0
        self._submit_t: Dict[int, float] = {}
        self._store_fp: Optional[str] = None
        # streaming state: queued/running outputs, claimed-on-drain results,
        # lifecycle status/error, absolute deadlines, rid -> slot map
        self._sched = Scheduler(self.policy, max_queue=self.max_queue,
                                shed_policy=self.shed_policy,
                                tenant_weights=self.tenant_weights,
                                retry_hint=self.retry_after_hint)
        self._next_rid = 0
        self._req: Dict[int, Request] = {}
        self._out: Dict[int, List[int]] = {}
        self._finished: Dict[int, List[int]] = {}
        self._status: Dict[int, str] = {}
        self._error: Dict[int, Optional[str]] = {}
        self._deadline: Dict[int, float] = {}
        self._rid_slot: Dict[int, int] = {}
        self._reset_slots()

    # -- compile accounting -------------------------------------------------
    @property
    def max_prefill_variants(self) -> int:
        """Upper bound on distinct prefill executables over the lifetime."""
        return sum(map(len, self.prefill_batch_buckets.values()))

    @property
    def max_decode_variants(self) -> int:
        """Upper bound on distinct decode executables over the lifetime."""
        return len(self.decode_buckets)

    @property
    def prefill_compiles(self) -> int:
        return int(self._prefill._cache_size())

    @property
    def decode_compiles(self) -> int:
        return int(self._decode._cache_size())

    # -- host-side slot state ----------------------------------------------
    def _reset_slots(self):
        B = self.batch
        self.cache = self.runner.init_state(B)
        self._active = np.zeros(B, bool)
        self._slot_req: List[Optional[int]] = [None] * B
        self._slot_rng: List[Optional[np.random.Generator]] = [None] * B
        self._slot_pos = np.zeros(B, np.int32)
        self._slot_last = np.zeros(B, np.int32)
        self._slot_left = np.zeros(B, np.int64)
        # prefix-cache state: resident prompt per slot, block-aligned
        # prefix index (LRU), donor refcounts, recency clock
        self._slot_prompt: List[Optional[np.ndarray]] = [None] * B
        self._slot_refs = np.zeros(B, np.int64)
        self._slot_touch = np.zeros(B, np.int64)
        self._prefix_index: "OrderedDict[Tuple[int, bytes], int]" = \
            OrderedDict()
        self._clock = 0

    # -- prefix index -------------------------------------------------------
    def _index_drop_slot(self, slot: int, *, spill: bool = True) -> None:
        """Evict a slot's rows from the prefix index — called exactly when
        the rows are about to be overwritten (slot reassigned to a new
        request, or borrowed as a decode pad lane). Rows referenced by an
        in-flight prefill are pinned and must never get here.

        With a ``prefix_store`` attached the evicted donor's rows are
        spilled to the host store first (this is the last moment they are
        readable — the overwrite follows immediately), except when
        ``spill=False``: scrub paths evict *poisoned* rows that must not
        outlive the engine."""
        assert self._slot_refs[slot] == 0, (
            f"evicting donor slot {slot} with {self._slot_refs[slot]} "
            f"in-flight references"
        )
        if self._slot_prompt[slot] is None:
            return
        if spill and self.prefix_store is not None:
            rows = jax.tree_util.tree_map(
                np.asarray,
                self.runner.gather_state(
                    self.cache, jnp.asarray([slot], jnp.int32)))
            if self.prefix_store.put(self._slot_prompt[slot],
                                     flatten_state_tree(rows),
                                     self._store_fingerprint()):
                self.stats.prefix_spills += 1
        self._slot_prompt[slot] = None
        for key in [k for k, s in self._prefix_index.items() if s == slot]:
            del self._prefix_index[key]

    def _index_insert(self, slot: int, prompt: np.ndarray) -> None:
        """Register a freshly-prefilled slot as a donor: every block-aligned
        prefix of its prompt maps to the slot. The index is LRU-bounded by
        ``prefix_capacity`` (forgetting an entry never frees slot rows).

        Gated on the runner's ``supports_prefix_cache`` as well as the
        engine flag: recurrent/enc-dec state has no per-position rows to
        donate, so indexing those prompts would promise copies the runner
        cannot make."""
        if not self.prefix_cache or not self.runner.supports_prefix_cache:
            return
        self._slot_prompt[slot] = prompt
        self._clock += 1
        self._slot_touch[slot] = self._clock
        raw = prompt.tobytes()                 # one serialization, sliced
        for m in range(self.prefix_block, prompt.shape[0] + 1,
                       self.prefix_block):
            key = (m, raw[: m * prompt.itemsize])
            self._prefix_index[key] = slot
            self._prefix_index.move_to_end(key)
        while len(self._prefix_index) > self.prefix_capacity:
            self._prefix_index.popitem(last=False)

    def _match_prefix(self, prompt: np.ndarray) -> Tuple[Optional[int], int]:
        """Longest usable indexed prefix of ``prompt``: match lengths are
        multiples of ``prefix_block``, capped at ``L - 1`` (the tail must
        produce the first-token logits) and by ``m + tail_bucket <=
        cache_len`` (the tail's pad ring slots must stay clear of the
        copied donor rows). Returns ``(donor_slot, m)`` or ``(None, 0)``."""
        if not self.prefix_cache or not self.runner.supports_prefix_cache \
                or not self._prefix_index:
            return None, 0
        L = int(prompt.shape[0])
        raw = prompt.tobytes()                 # one serialization, sliced
        m = ((L - 1) // self.prefix_block) * self.prefix_block
        while m >= self.prefix_block:
            key = (m, raw[: m * prompt.itemsize])
            slot = self._prefix_index.get(key)
            if slot is not None:
                Sb = pick_bucket(L - m, self.prompt_buckets)
                if m + Sb <= self.cache_len:
                    self._prefix_index.move_to_end(key)
                    self._clock += 1
                    self._slot_touch[slot] = self._clock
                    return int(slot), m
            m -= self.prefix_block
        return None, 0

    def _store_fingerprint(self) -> str:
        """Geometry identity for prefix-store entries: runner class,
        cache_len, and the single-slot gathered-state leaf shapes/dtypes
        (via ``eval_shape`` — no compute). Adopting rows produced under a
        different geometry raises in the store instead of silently
        placing mismatched state."""
        if self._store_fp is None:
            shaped = jax.eval_shape(
                lambda c: self.runner.gather_state(
                    c, jnp.zeros((1,), jnp.int32)), self.cache)
            leaves = [(list(l.shape), str(l.dtype))
                      for l in jax.tree_util.tree_leaves(shaped)]
            self._store_fp = json.dumps(
                {"runner": type(self.runner).__name__,
                 "cache_len": self.cache_len, "leaves": leaves},
                sort_keys=True)
        return self._store_fp

    def adopt_prefixes(self, max_slots: Optional[int] = None) -> int:
        """Warm-start free slots from the attached ``prefix_store``:
        place the hottest stored donor rows into unowned, unindexed,
        unpinned slots and register them in the prefix index, so the next
        admission round's ``_match_prefix`` finds them resident. Returns
        the number of slots adopted. The supervisor calls this after
        building/restoring a replacement engine; callers may also invoke
        it on a cold engine before traffic.

        Uses the same runner ops as serving (``place_state`` is the
        prefill donor-copy primitive), so adopted rows are bit-identical
        to the rows the original engine held — greedy outputs after a
        prefix hit on an adopted donor match the original engine's.
        """
        self._check_alive()
        if self.prefix_store is None or not self.prefix_cache:
            return 0
        budget = self.batch if max_slots is None else int(max_slots)
        free = [s for s in range(self.batch)
                if not self._active[s] and self._slot_refs[s] == 0
                and self._slot_prompt[s] is None]
        adopted = 0
        for prompt, rows in self.prefix_store.hottest():
            if not free or adopted >= budget:
                break
            if prompt.shape[0] > self.cache_len:
                continue
            # already resident? (a restored engine may still hold it)
            raw = prompt.tobytes()
            mtop = (prompt.shape[0] // self.prefix_block) \
                * self.prefix_block
            if mtop >= self.prefix_block and \
                    (mtop, raw[: mtop * prompt.itemsize]) \
                    in self._prefix_index:
                continue
            # geometry guard: the store fingerprint was checked at put
            # time, but a hand-loaded store meets the engine here
            self.prefix_store._check_fingerprint(
                self._store_fingerprint(), "adopt")
            sub = unflatten_state_tree(
                self.runner.init_state(1),
                {k: v for k, v in rows.items()})
            slot = free.pop(0)
            self.cache = self.runner.place_state(
                self.cache, sub, jnp.asarray([slot], jnp.int32))
            self._index_insert(slot, prompt)
            self.prefix_store.touch(prompt)
            adopted += 1
            self.stats.prefix_adoptions += 1
        return adopted

    # -- backpressure -------------------------------------------------------
    def retry_after_hint(self) -> Optional[float]:
        """Estimated seconds until a queue slot frees: queue depth over
        the recently-observed drain rate (terminals/sec EWMA across step
        boundaries). ``None`` until the engine has observed any drain —
        callers fall back to their own backoff. Attached to every
        :class:`QueueFullError` the scheduler raises."""
        if self._drain_rate <= 0.0:
            return None
        depth = max(1, len(self._sched))
        return float(min(60.0, max(1e-3, depth / self._drain_rate)))

    def _observe_drain(self, now: float) -> None:
        """EWMA the terminal-completion rate at each step boundary.
        Terminals accumulate until the clock actually advances (dt > 0) —
        zero-dt steps must not swallow completions into the baseline, or
        a whole burst finishing inside one clock tick would never
        register as drain."""
        if self._prev_step_t is None:
            self._prev_step_t = now
            return
        dt = now - self._prev_step_t
        if dt <= 0:
            return
        rate = (self._terminals - self._prev_terminals) / dt
        a = 0.2
        self._drain_rate = (rate if self._drain_rate == 0.0
                            else a * rate + (1 - a) * self._drain_rate)
        self._prev_step_t = now
        self._prev_terminals = self._terminals

    def _validate(self, r: Request) -> None:
        _validate_request(r, self.cache_len)
        self.runner.validate_request(r)

    # -- lifecycle ----------------------------------------------------------
    def _check_alive(self) -> None:
        if self._fatal is not None:
            raise EngineFatalError(
                f"engine is dead ({self._fatal}); build a replacement "
                f"engine and restore() its latest snapshot"
            )

    def _die(self, e: BaseException) -> None:
        """Engine-fatal error: a launch may have consumed its donated cache
        buffer partway, so no device state can be trusted. Mark the engine
        dead (every subsequent submit/step refuses) and raise."""
        self._fatal = f"{type(e).__name__}: {e}"
        raise EngineFatalError(
            f"engine-fatal serving error ({self._fatal}): donated device "
            f"buffers cannot be trusted after a mid-launch failure — the "
            f"engine is dead; build a replacement engine and restore() its "
            f"latest snapshot"
        ) from e

    def _scrub_slot(self, slot: int) -> None:
        """Overwrite a slot's cache rows with blank (fresh) rows. Needed
        after a non-finite launch row: NaN k/v entries contaminate any
        later read through attention even when masked (``0 · NaN = NaN``),
        including the no-match self-donor seed of the next prefill."""
        idx = jnp.asarray([slot], jnp.int32)
        self.cache = self.runner.reset_rows(self.cache, idx)

    def _finalize(self, rid: int, status: str,
                  error: Optional[str] = None, *,
                  scrub: bool = False) -> None:
        """Move a request to a terminal state. Frees its slot if admitted
        (donor refcounts are zero whenever this runs — step boundaries and
        post-launch paths only), keeps the slot's prefix-index entries
        unless ``scrub`` (non-finite rows: drop from the index AND blank
        the rows), and bumps the matching stats counter. The (possibly
        partial) tokens stay claimable via ``drain``."""
        assert status in TERMINAL_STATES, status
        slot = self._rid_slot.pop(rid, None)
        if slot is not None:
            self._active[slot] = False
            self._slot_req[slot] = None
            self._slot_rng[slot] = None
            if scrub:
                # poisoned rows: never spill them to the prefix store
                self._index_drop_slot(slot, spill=False)
                self._scrub_slot(slot)
        req = self._req.pop(rid, None)
        self._finished[rid] = self._out.pop(rid, [])
        self._deadline.pop(rid, None)
        self._submit_t.pop(rid, None)
        self._status[rid] = status
        self._error[rid] = error
        self._terminals += 1
        ts = (self.stats.tenant(req.tenant) if req is not None else None)
        if status == FINISHED:
            self.stats.requests_completed += 1
            if ts is not None:
                ts.completed += 1
        elif status == FAILED:
            self.stats.aborted += 1
            if ts is not None:
                ts.aborted += 1
        elif status == EXPIRED:
            self.stats.expired += 1
            if ts is not None:
                ts.expired += 1
        elif status == CANCELLED:
            self.stats.cancelled += 1
            if ts is not None:
                ts.cancelled += 1

    def _expire_overdue(self) -> None:
        """Step-boundary deadline watchdog: EXPIRE every request (queued or
        running) whose ``deadline_ms`` has elapsed. Runs at step boundaries
        only, where donor refcounts are all zero — slot recycling is always
        safe and the slot's prefix-index entries stay valid."""
        if not self._deadline:
            return
        now = self._clock_fn()
        for rid in [r for r, t in self._deadline.items() if now >= t]:
            r = self._req.get(rid)
            ms = None if r is None else r.deadline_ms
            self._finalize(rid, EXPIRED,
                           f"deadline_ms={ms} exceeded at step boundary")

    def _push_token(self, slot: int, logits_row: np.ndarray) -> None:
        rid = self._slot_req[slot]
        r = self._req[rid]
        tok = _sample_token(logits_row, r.sampling, self._slot_rng[slot])
        if r.stop_tokens and tok in r.stop_tokens:
            self._finalize(rid, FINISHED)
            return
        # SLO instrumentation: first emitted token closes the TTFT window
        # (submit -> first token)
        if not self._out[rid]:
            t0 = self._submit_t.get(rid)
            if t0 is not None:
                ttft = (self._clock_fn() - t0) * 1e3
                self.stats.ttft_ms.observe(ttft)
                self.stats.tenant(r.tenant).ttft_ms.observe(ttft)
        self._out[rid].append(tok)
        self.stats.tokens_generated += 1
        self.stats.tenant(r.tenant).tokens += 1
        self._slot_last[slot] = tok
        self._slot_left[slot] -= 1
        if self._slot_left[slot] <= 0:
            self._finalize(rid, FINISHED)

    # -- admission ----------------------------------------------------------
    def _resolve_placement(self, rids: List[int],
                           match: Dict[int, Tuple[Optional[int], int]],
                           free: List[int]):
        """Resolve this round's slot placement under donor pins.

        Placement pool = free slots with no in-flight references. When
        pinned free donors starve it: a donor with a SINGLE consumer hosts
        that consumer itself (the row copy and the overwrite happen in one
        launch — no other launch reads it); other consumers are DEFERRED
        to the next round (put_front: they re-match against the same
        resident donors) rather than burn their matches; if a round would
        otherwise admit nothing, matches are dropped — progress always
        wins over reuse.

        Returns ``(keep, avail, self_place)``: the requests to admit, an
        ordered slot pool covering all of them, and per-request
        self-placement onto their own donor. Pin invariant on return:
        every remaining pin belongs to a kept request's match and is
        released right after the launch that consumes it.
        """
        n = len(rids)
        avail = [i for i in free if self._slot_refs[i] == 0]
        self_place: Dict[int, int] = {}
        if len(avail) >= n:
            return rids, avail, self_place
        keep = list(rids)
        deferred: List[int] = []
        for rid in reversed(rids):
            if len(avail) + len(self_place) >= len(keep):
                break
            donor, _ = match[rid]
            if donor is None or self._active[donor]:
                continue
            if self._slot_refs[donor] == 1:
                self_place[rid] = donor            # sole consumer: host it
                continue
            if len(keep) == 1:
                continue
            keep.remove(rid)
            deferred.append(rid)
            match.pop(rid)
            self._slot_refs[donor] -= 1
            if self._slot_refs[donor] == 0:
                avail.append(donor)
        if len(avail) + len(self_place) < len(keep):
            # still starved (defensive): give up matches (full prefill)
            # so the round still admits
            for rid in keep:
                donor, _ = match[rid]
                if donor is None or self._active[donor] \
                        or rid in self_place:
                    continue
                self._slot_refs[donor] -= 1
                match[rid] = (None, 0)
                if self._slot_refs[donor] == 0:
                    avail.append(donor)
                if len(avail) + len(self_place) >= len(keep):
                    break
        # deferred holds latest-taken first; pushing in that order leaves
        # the earliest-taken at the queue head (original order)
        for rid in deferred:
            self._sched.put_front(rid, self._req[rid].prompt_len,
                                  tenant=self._req[rid].tenant)
        return keep, avail, self_place

    def _on_launch(self, kind: str, index: int, rids) -> None:
        """Fault-injection hook with a tenant-aware audit: pass the sorted
        tenant set riding in the launch when the injector understands it
        (``accepts_tenants``); plain two-argument injectors keep working."""
        if self.faults is None:
            return
        if getattr(self.faults, "accepts_tenants", False):
            tenants = tuple(sorted({self._req[rid].tenant for rid in rids
                                    if rid in self._req}))
            self.faults.on_launch(kind, index, tenants=tenants)
        else:
            self.faults.on_launch(kind, index)

    def _chunk_inputs(self, chunk: List[int], Bb: int, Sb: int, match,
                      self_place: Dict[int, int], avail: List[int]):
        """Host inputs of one prefill launch: the chunk's slots (taken from
        ``avail`` unless self-placed), left-padded tokens and positions,
        prefix-cache donors and the optional keyword operands."""
        slots = []
        for rid in chunk:
            s = self_place.get(rid)
            if s is None:
                s = avail.pop(0)
            else:
                # the consumer's own pin; released before eviction
                # so _index_drop_slot sees an unreferenced slot
                self._slot_refs[s] -= 1
            slots.append(s)
        toks = np.zeros((Bb, Sb), np.int32)
        pos = np.zeros((Bb, Sb), np.int32)
        donor_idx = np.asarray(slots, np.int32).copy()
        mlen = np.zeros(Bb, np.int32)
        prompts: List[np.ndarray] = []
        for j, rid in enumerate(chunk):
            p = np.asarray(self._req[rid].prompt, np.int32).reshape(-1)
            prompts.append(p)
            donor, m = match[rid]
            T = p.shape[0] - m
            toks[j, Sb - T:] = p[m:]
            if m > 0:
                # tail continues at positions m..m+T-1; pad writes
                # park on ring slots m+T..m+Sb-1 with NEGATIVE
                # stored positions (masked), clear of the copied
                # donor rows [0, m)
                pos[j, Sb - T:] = m + np.arange(T, dtype=np.int32)
                pos[j, : Sb - T] = (
                    m + T + np.arange(Sb - T, dtype=np.int32)
                    - self.cache_len)
                donor_idx[j] = donor
                mlen[j] = m
                self.stats.prefix_hits += 1
                self.stats.prefill_tokens_saved += int(m)
            else:
                # pads get negative positions -> attention-masked
                pos[j] = np.arange(Sb, dtype=np.int32) - (Sb - T)
            self.stats.padded_prompt_tokens += Sb - T
        for slot in slots:
            self._index_drop_slot(slot)   # rows being overwritten
        # the optional parts ride as kwargs so the positional
        # layout (donated state at 3) is constant across runners;
        # the kwarg set is fixed per engine configuration, so the
        # jit cache still sees one calling convention
        kw = {}
        if self.prefix_cache:
            kw["donor_idx"] = jnp.asarray(donor_idx)
            kw["match_len"] = jnp.asarray(mlen)
        if self.runner.requires_extra:
            kw["extra"] = jnp.asarray(np.stack([
                np.asarray(self._req[rid].extra, np.float32)
                for rid in chunk]))
        return slots, toks, pos, prompts, kw

    def _admit(self) -> None:
        with TraceAnnotation("serve.admit", step=self._step_count):
            free = [i for i in range(self.batch) if not self._active[i]]
            if not free:
                return
            # take from the queue, lazily skipping stale entries (requests
            # cancelled / expired / shed while still queued stay in the
            # heap until taken here — O(1) amortized instead of eager heap
            # surgery)
            rids: List[int] = []
            while len(rids) < len(free) and len(self._sched):
                for rid in self._sched.take(len(free) - len(rids)):
                    if rid in self._finished:
                        continue
                    rids.append(rid)
            if not rids:
                return
            # prefix matching against the RESIDENT index (donors placed in
            # earlier rounds — active or finished-but-unreclaimed slots); a
            # matched donor is pinned until the launch that copies it has
            # run
            match: Dict[int, Tuple[Optional[int], int]] = {}
            for rid in rids:
                p = np.asarray(self._req[rid].prompt, np.int32).reshape(-1)
                donor, m = self._match_prefix(p)
                match[rid] = (donor, m)
                if donor is not None:
                    self._slot_refs[donor] += 1
            rids, avail, self_place = self._resolve_placement(
                rids, match, free)
            if self.prefix_cache:
                # lookups count ADMITTED requests only (deferred ones
                # re-match next round; counting both would dilute the rate)
                self.stats.prefix_lookups += len(rids)
            by_bucket: Dict[int, List[int]] = {}
            for rid in rids:
                tail = self._req[rid].prompt_len - match[rid][1]
                Sb = pick_bucket(tail, self.prompt_buckets)
                by_bucket.setdefault(Sb, []).append(rid)
        for Sb in sorted(by_bucket):
            rids_b = by_bucket[Sb]
            for Bb in batch_split(len(rids_b),
                                  self.prefill_batch_buckets[Sb]):
                chunk, rids_b = rids_b[:Bb], rids_b[Bb:]
                with TraceAnnotation("serve.admit", step=self._step_count):
                    slots, toks, pos, prompts, kw = self._chunk_inputs(
                        chunk, Bb, Sb, match, self_place, avail)
                try:
                    self._on_launch("prefill", self.stats.prefill_calls,
                                    chunk)
                    with TraceAnnotation("serve.prefill.launch",
                                         step=self._step_count,
                                         rows=int(Bb), bucket=int(Sb)):
                        logits, ok, self.cache = self._prefill(
                            self.params, jnp.asarray(toks),
                            jnp.asarray(pos), self.cache,
                            jnp.asarray(np.asarray(slots, np.int32)), **kw)
                # lint: allow-broad-except — fault-isolation boundary:
                # classify_error decides request-fatal vs engine-fatal
                except BaseException as e:
                    if classify_error(e) != "request":
                        self._die(e)
                    # transient fault BEFORE the executable ran: buffers
                    # intact, slot rows untouched (still free, already out
                    # of the prefix index). Release this chunk's donor pins
                    # and FAIL only its requests; later chunks continue.
                    for rid in chunk:
                        donor, _ = match[rid]
                        if donor is not None and rid not in self_place:
                            self._slot_refs[donor] -= 1
                        self._finalize(rid, FAILED,
                                       f"prefill launch failed: {e}")
                    continue
                # copies landed: release this chunk's donor pins
                # (self-placed consumers already released theirs)
                for rid in chunk:
                    donor, _ = match[rid]
                    if donor is not None and rid not in self_place:
                        self._slot_refs[donor] -= 1
                self.stats.prefill_calls += 1
                self.stats.prefill_shapes.add((Bb, Sb))
                with TraceAnnotation("serve.prefill.fetch",
                                     step=self._step_count):
                    lg = np.asarray(logits)
                    okh = np.asarray(ok)
                with TraceAnnotation("serve.prefill.sample",
                                     step=self._step_count):
                    for j, (slot, rid) in enumerate(zip(slots, chunk)):
                        if not okh[j]:
                            # poisoned row: its NaN k/v already landed in
                            # the slot — scrub back to blank rows (a masked
                            # NaN still reaches attention via 0·NaN) and
                            # never index/activate. Other rows are
                            # unaffected.
                            self._scrub_slot(slot)
                            self._finalize(
                                rid, FAILED, "non-finite logits in prefill "
                                "(request aborted; batch continues)")
                            continue
                        r = self._req[rid]
                        self.stats.tenant(r.tenant).admitted += 1
                        self._index_insert(slot, prompts[j])
                        self._slot_req[slot] = rid
                        self._rid_slot[rid] = slot
                        self._slot_rng[slot] = r.sampling.make_rng()
                        self._slot_pos[slot] = r.prompt_len
                        self._slot_left[slot] = r.max_new
                        self._active[slot] = True
                        self._push_token(slot, lg[j])

    # -- decode -------------------------------------------------------------
    def _decode_rows(self, act: np.ndarray) -> Tuple[int, np.ndarray]:
        """The decode bucket for the active slots ``act`` and the slot rows
        it launches: ``act`` first, then pad lanes."""
        n = act.size
        Bb = pick_bucket(n, self.decode_buckets)
        # pad lanes borrow *distinct free* slot rows (there are always
        # enough: Bb <= batch so Bb - n <= batch - n). The in-place decode
        # therefore writes no row twice, and pad-lane writes land on
        # dead rows that the next admission's prefill fully overwrites.
        # With the prefix cache on, free rows may be resident donors whose
        # rows are still valuable: borrow non-donor rows first, and evict
        # (least-recently-used first) any donor row that must be borrowed —
        # its rows are about to take an unmasked pad write.
        idx = act
        if Bb > n:
            free = np.nonzero(~self._active)[0]
            if self.prefix_cache:
                plain = [int(i) for i in free
                         if self._slot_prompt[i] is None]
                donors = sorted(
                    (int(i) for i in free
                     if self._slot_prompt[i] is not None),
                    key=lambda s: self._slot_touch[s])
                borrow = (plain + donors)[: Bb - n]
                for s in borrow:
                    self._index_drop_slot(s)
                idx = np.concatenate([act, np.asarray(borrow, act.dtype)])
            else:
                idx = np.concatenate([act, free[: Bb - n]])
        idx = idx.astype(np.int32)
        return Bb, idx

    def _decode_step(self) -> None:
        with TraceAnnotation("serve.decode.prep", step=self._step_count):
            act = np.nonzero(self._active)[0]
            n = act.size
            if n == 0:
                return
            Bb, idx = self._decode_rows(act)
            pos = self._slot_pos[idx]
            # cache positions a read bounded per row needs: the row's
            # filled ring slots, the new entry's included
            ctx = int(np.clip(pos + 1, 0, self.cache_len).sum())
        # wrapped launch with ONE retry for transient (pre-launch) faults:
        # the injector's fired-set guarantees a scheduled fault does not
        # refire, so the retry runs the same launch with intact buffers. A
        # second failure — or any error that may have consumed the donated
        # cache mid-execution — is engine-fatal (snapshot/restore path).
        attempt = 0
        while True:
            try:
                self._on_launch("decode", self.stats.decode_steps,
                                [self._slot_req[int(s)] for s in act])
                with TraceAnnotation("serve.decode.launch",
                                     step=self._step_count,
                                     rows=int(Bb), ctx=ctx):
                    logits, ok, self.cache = self._decode(
                        self.params,
                        jnp.asarray(self._slot_last[idx][:, None]),
                        self.cache, jnp.asarray(pos),
                        jnp.asarray(idx),
                    )
                break
            # lint: allow-broad-except — fault-isolation boundary:
            # classify_error decides retry vs engine-fatal
            except BaseException as e:
                if classify_error(e) != "request" or attempt >= 1:
                    self._die(e)
                attempt += 1
                self.stats.launch_retries += 1
        self.stats.decode_steps += 1
        self.stats.slot_steps_active += int(n)
        self.stats.decode_rows += int(Bb)
        self.stats.decode_ctx_positions += ctx
        self.stats.decode_shapes.add(int(Bb))
        self._slot_pos[act] += 1
        with TraceAnnotation("serve.decode.fetch", step=self._step_count):
            lg = np.asarray(logits)
            okh = np.asarray(ok)
        with TraceAnnotation("serve.decode.sample", step=self._step_count):
            for j, slot in enumerate(act):
                slot = int(slot)
                if not okh[j]:
                    # poisoned row: abort just this request; scrub its
                    # rows (NaN k/v reach attention even masked) and drop
                    # it from the prefix index. All other rows continue
                    # unaffected.
                    self._finalize(self._slot_req[slot], FAILED,
                                   "non-finite logits in decode "
                                   "(request aborted; batch continues)",
                                   scrub=True)
                    continue
                self._push_token(slot, lg[j])

    def audit(self, raise_on_violation: bool = False):
        """Run every single-engine structural contract (see the module
        docstring's *Structural contracts* section) and return the
        violations — an empty list is the pass condition. With
        ``raise_on_violation=True`` a non-empty result raises
        :class:`~repro.analysis.contracts.StructuralContractError` whose
        message carries per-violation ``file:line`` provenance."""
        from repro.analysis.contracts import (StructuralContractError,
                                              audit_engine)

        violations = audit_engine(self)
        if raise_on_violation and violations:
            raise StructuralContractError(violations)
        return violations

    def prewarm(self, audit: bool = False,
                max_prompt_bucket: Optional[int] = None) -> int:
        """Compile every (batch-bucket, prompt-bucket) prefill executable
        plus every decode-bucket executable up front, so steady-state
        serving never recompiles. Possible precisely because the bucket
        grid is finite — the wave baseline has no analogue (one executable
        per distinct wave length it happens to see). Returns the number of
        live executables. ``max_prompt_bucket`` leaves out the prompt
        buckets above it, for traffic known to send no longer prompt (a
        longer one would compile when it first arrives).

        ``audit=True`` gates compilation on the structural contracts: the
        bucketed executables are traced and audited first (``audit()``),
        and any violation raises before a single XLA compile is spent on a
        structurally broken program.

        Warm-up results are COMMITTED, not discarded: the cache argument is
        donated (``donate_argnums``), so the input buffer is invalid after
        every call and discarding the returned handle would kill the live
        cache. Commitment is safe because every warm-up write is masked
        (all-pad prefill rows; decode probes at position ``-1``) — but it
        does touch free slot rows, so prewarm requires an IDLE engine (no
        active slots) and flushes the prefix index (resident donor rows in
        free slots take pad writes).
        """
        self._check_alive()
        if self._active.any():
            raise RuntimeError(
                "prewarm() requires an idle engine: warm-up launches commit "
                "(masked) writes into slot rows that active requests own"
            )
        if audit:
            self.audit(raise_on_violation=True)
        if self.prefix_cache:
            for s in range(self.batch):
                self._index_drop_slot(s)
        for Sb in self.prompt_buckets:
            if max_prompt_bucket is not None and Sb > max_prompt_bucket:
                continue
            for Bb in self.prefill_batch_buckets[Sb]:
                toks = jnp.zeros((Bb, Sb), jnp.int32)
                # all-pad rows (every position negative): fully masked,
                # mathematically defined, and shape-identical to real traffic
                pos = (jnp.broadcast_to(jnp.arange(Sb, dtype=jnp.int32),
                                        (Bb, Sb)) - Sb)
                slots = jnp.arange(Bb, dtype=jnp.int32)
                kw = {}
                if self.prefix_cache:
                    # self-donor with match 0: fully-masked seed, same
                    # calling convention (and executable) as real traffic
                    kw["donor_idx"] = slots
                    kw["match_len"] = jnp.zeros((Bb,), jnp.int32)
                ex = self.runner.prewarm_extra(Bb)
                if ex is not None:
                    kw["extra"] = ex
                _, _, self.cache = self._prefill(
                    self.params, toks, pos, self.cache, slots, **kw)
        for Bb in self.decode_buckets:
            # probe at position -1: the ring write lands with a negative
            # stored position (masked), so committing the returned cache
            # leaves the math untouched
            _, _, self.cache = self._decode(
                self.params, jnp.zeros((Bb, 1), jnp.int32), self.cache,
                -jnp.ones((Bb,), jnp.int32),
                jnp.arange(Bb, dtype=jnp.int32),
            )
        return self.prefill_compiles + self.decode_compiles

    # -- public API ---------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Enqueue one request for service; returns its request id. The
        request is admitted to a cache slot by a later ``step()`` (or
        ``drain``/``generate``) as slots free up.

        With ``max_queue`` set, a submit at the bound either raises
        :class:`QueueFullError` (``shed_policy="reject"`` — nothing is
        enqueued, ``stats.rejected`` counts it; retry after draining) or
        sheds the longest-queued request as CANCELLED
        (``"drop-oldest"``). The deadline clock starts now."""
        self._check_alive()
        self._validate(request)
        if self._sched.max_queue is not None:
            # stale heap entries (cancelled/expired while queued) must not
            # count against the bound
            self._sched.purge(lambda rid: rid not in self._finished)
        rid = self._next_rid
        try:
            dropped = self._sched.submit(rid, request.prompt_len,
                                         tenant=request.tenant)
        except QueueFullError:
            self.stats.rejected += 1
            self.stats.tenant(request.tenant).rejected += 1
            raise
        self._next_rid += 1
        self._req[rid] = request
        self._out[rid] = []
        self._submit_t[rid] = self._clock_fn()
        self.stats.tenant(request.tenant).submitted += 1
        if request.deadline_ms is not None:
            self._deadline[rid] = (self._clock_fn()
                                   + request.deadline_ms / 1000.0)
        if dropped is not None:
            self.stats.rejected += 1
            self._finalize(dropped, CANCELLED,
                           "load shed (drop-oldest): queue at max_queue="
                           f"{self._sched.max_queue}")
        return rid

    def cancel(self, req_id: int) -> bool:
        """Cancel a queued or running request: its slot (if any) is
        recycled and its partial tokens stay claimable via ``drain``.
        Returns True if this call cancelled it, False if it was already
        terminal; raises ``KeyError`` for unknown/claimed ids."""
        if req_id in self._finished:
            return False
        if req_id not in self._out:
            raise KeyError(f"unknown or already-claimed request id {req_id}")
        self._finalize(req_id, CANCELLED, "cancelled by caller")
        return True

    def step(self) -> bool:
        """Advance the engine one round: expire overdue deadlines (step-
        boundary watchdog), admit queued requests into free slots (bucketed
        prefill), and run one compacted decode step. Auto-snapshots every
        ``snapshot_every`` steps. Returns True while work remains (active
        slots or queued requests). Raises :class:`EngineFatalError` (and
        marks the engine dead) on unrecoverable launch errors."""
        with TraceAnnotation("serve.step", step=self._step_count):
            self._check_alive()
            t0 = self._clock_fn()
            if self.faults is not None:
                self.faults.on_step(self._step_count)
            self._expire_overdue()
            self._admit()
            self._decode_step()
            self._step_count += 1
            now = self._clock_fn()
            self._observe_drain(now)
            if self._watchdog.observe(self._step_count, now - t0) != "ok":
                self.stats.slow_steps += 1
            # auto-snapshot skips an EMPTY engine (no queued, running, or
            # unclaimed requests): such a snapshot resumes nothing —
            # restoring it is refused — and idle-loop callers would
            # otherwise overwrite the last useful snapshot with a useless
            # one
            if (self.snapshot_dir is not None and self.snapshot_every > 0
                    and self._step_count % self.snapshot_every == 0
                    and (self._req or self._finished)):
                self.snapshot()
            return bool(self._active.any() or len(self._sched))

    def poll(self, req_id: int) -> RequestState:
        """Snapshot a submitted request's progress without consuming it:
        tokens generated so far, lifecycle ``status``, and the ``error``
        reason for failed terminals. Raises ``KeyError`` for unknown or
        already-claimed (drained) request ids."""
        if req_id in self._finished:
            return RequestState(req_id, True, tuple(self._finished[req_id]),
                                self._status.get(req_id, FINISHED),
                                self._error.get(req_id))
        if req_id in self._out:
            status = RUNNING if req_id in self._rid_slot else QUEUED
            return RequestState(req_id, False, tuple(self._out[req_id]),
                                status, None)
        raise KeyError(
            f"unknown or already-claimed request id {req_id}"
        )

    def drain(self, req_ids: Optional[Sequence[int]] = None
              ) -> Dict[int, List[int]]:
        """Run ``step()`` until the engine is idle, then claim finished
        outputs: the requested ids (default: every unclaimed terminal
        request) are removed from the engine and returned as
        ``{req_id: tokens}`` — partial tokens for FAILED/EXPIRED/CANCELLED
        terminals (``poll`` first for the status). Unlisted terminal
        requests stay pollable."""
        while self.step():
            pass
        if req_ids is None:
            req_ids = list(self._finished)
        # validate every id (and reject duplicates) BEFORE popping any, so a
        # bad id cannot discard other requests' already-claimed outputs
        rids = list(req_ids)
        if len(set(rids)) != len(rids):
            raise KeyError(f"duplicate request ids in drain: {rids}")
        for rid in rids:
            if rid not in self._finished:
                raise KeyError(
                    f"request id {rid} is not a finished unclaimed request"
                )
        out = {}
        for rid in rids:
            out[rid] = self._finished.pop(rid)
            self._status.pop(rid, None)
            self._error.pop(rid, None)
        return out

    def generate(self, requests: List[Request]) -> List[List[int]]:
        """Serve a list of requests; returns per-request tokens, in request
        order. A thin wrapper over the streaming loop: submit all, drain to
        idle, claim this call's outputs (earlier ``submit``-ed requests also
        run to completion but stay pollable/claimable). Admission
        interleaves with decoding: slots refill as soon as their request
        finishes (continuous batching).

        Backpressure is absorbed internally: a submit rejected at the
        ``max_queue`` bound steps the engine (freeing queue space) and
        retries — the loop always terminates because every queued request
        has a finite budget. Under ``drop-oldest``, shed requests of this
        call return their (possibly empty) partial tokens."""
        # validate the whole batch before submitting any of it: a bad
        # request must not leave its predecessors enqueued as ghost work
        for r in requests:
            self._validate(r)
        rids = []
        for r in requests:
            while True:
                try:
                    rids.append(self.submit(r))
                    break
                except QueueFullError:
                    self.step()
        done = self.drain(rids)
        return [done[rid] for rid in rids]

    # -- snapshot / restore -------------------------------------------------
    _STAT_FIELDS = (
        "prefill_calls", "decode_steps", "tokens_generated",
        "requests_completed", "padded_prompt_tokens", "slot_steps_active",
        "decode_rows", "decode_ctx_positions", "prefix_lookups",
        "prefix_hits",
        "prefill_tokens_saved", "rejected", "aborted", "expired",
        "cancelled", "recoveries", "snapshots", "launch_retries",
        "slow_steps", "prefix_spills", "prefix_adoptions",
    )

    def _fingerprint(self) -> Dict[str, object]:
        """Configuration identity a snapshot is only valid against."""
        return {
            "batch": self.batch, "cache_len": self.cache_len,
            "runner": type(self.runner).__name__,
            "policy": self.policy,
            "prompt_buckets": list(self.prompt_buckets),
            "decode_buckets": list(self.decode_buckets),
            "prefix_cache": self.prefix_cache,
            "prefix_block": self.prefix_block,
            "prefix_capacity": self.prefix_capacity,
            "vocab": int(self.cfg.vocab),
            "max_queue": self.max_queue,
            "shed_policy": self.shed_policy,
            "quantize": self.quantize,
            "tenant_weights": [[k, int(v)] for k, v in
                               sorted(self.tenant_weights.items())],
        }

    def frozen_table_bytes(self) -> int:
        """Resident bytes of the frozen frequency tables (incl. fused
        copies and quantization scales) — the quantization acceptance
        metric (int8 ≤ 0.55× fp32)."""
        from repro.kernels.block_circulant.plan import frozen_table_bytes

        return frozen_table_bytes(self.params)

    def snapshot(self) -> str:
        """Serialize the COMPLETE serving state — KV cache, slot table,
        scheduler queue, per-request outputs and RNG states, prefix index,
        deadlines (as remaining budget), stats — through ``ft.checkpoint``'s
        atomic tmp+rename machinery. A replacement engine with the same
        configuration ``restore()``s it and resumes every in-flight decode
        mid-stream; decoding is deterministic, so greedy outputs are
        bit-identical to an uninterrupted run. Returns the checkpoint path.

        Runs at step boundaries only (``step()`` auto-snapshots via
        ``snapshot_every``); donor refcounts are zero there, so the state
        is closed under restore."""
        self._check_alive()
        if self.snapshot_dir is None:
            raise ValueError("snapshot() needs snapshot_dir")
        assert (self._slot_refs == 0).all(), \
            "snapshot mid-admission: donor rows are pinned"
        now = self._clock_fn()
        extra_rids = sorted(rid for rid, r in self._req.items()
                            if r.extra is not None)
        meta = {
            "version": 3,
            "fingerprint": self._fingerprint(),
            "step_count": self._step_count,
            "next_rid": self._next_rid,
            "prefix_clock": self._clock,
            "extra_rids": extra_rids,
            "requests": [
                [rid, {
                    "prompt": np.asarray(r.prompt, np.int32)
                    .reshape(-1).tolist(),
                    "max_new": int(r.max_new),
                    "stop_tokens": list(r.stop_tokens),
                    "sampling": {
                        "temperature": float(r.sampling.temperature),
                        "top_k": int(r.sampling.top_k),
                        "seed": int(r.sampling.seed)},
                    "deadline_ms": r.deadline_ms,
                    "tenant": r.tenant,
                }] for rid, r in self._req.items()],
            "out": [[rid, list(t)] for rid, t in self._out.items()],
            "finished": [[rid, list(t), self._status.get(rid, FINISHED),
                          self._error.get(rid)]
                         for rid, t in self._finished.items()],
            "deadline_remaining_s": [[rid, max(0.0, t - now)]
                                     for rid, t in self._deadline.items()],
            # submit times as AGES (like deadlines): absolute clocks don't
            # survive process boundaries, relative ones do
            "timing": {
                "submit_age_s": [[rid, now - t]
                                 for rid, t in self._submit_t.items()],
            },
            "sched": self._sched.state_dict(),
            "rid_slot": [[rid, int(s)] for rid, s in self._rid_slot.items()],
            "slots": {
                "active": [bool(x) for x in self._active],
                "req": [None if x is None else int(x)
                        for x in self._slot_req],
                "pos": [int(x) for x in self._slot_pos],
                "last": [int(x) for x in self._slot_last],
                "left": [int(x) for x in self._slot_left],
                "touch": [int(x) for x in self._slot_touch],
                "prompt": [None if p is None else p.tolist()
                           for p in self._slot_prompt],
                "rng": [None if g is None else g.bit_generator.state
                        for g in self._slot_rng],
            },
            "prefix_index": [[int(m), raw.hex(), int(slot)]
                             for (m, raw), slot in
                             self._prefix_index.items()],
            "stats": {f: int(getattr(self.stats, f))
                      for f in self._STAT_FIELDS},
            "stats_shapes": {
                "prefill": sorted([int(b), int(s)]
                                  for b, s in self.stats.prefill_shapes),
                "decode": sorted(int(b)
                                 for b in self.stats.decode_shapes)},
            # fixed-bucket histograms serialize exactly: bucket counts in,
            # bucket counts out — restore resumes the same p50/p99
            "stats_hists": {
                "ttft": list(self.stats.ttft_ms.counts)},
            "stats_tenants": [
                [t, {"submitted": ts.submitted, "admitted": ts.admitted,
                     "completed": ts.completed, "rejected": ts.rejected,
                     "expired": ts.expired, "cancelled": ts.cancelled,
                     "aborted": ts.aborted, "tokens": ts.tokens,
                     "ttft": list(ts.ttft_ms.counts)}]
                for t, ts in sorted(self.stats.tenants.items())],
        }
        # the state tree is serialized OPAQUELY — flat canonical leaf
        # order, no knowledge of the family's tree shape (KV-cache group
        # lists, recurrent-state dicts, enc-dec layer stacks all work)
        state = {
            "cache": flatten_state_tree(self.cache),
            "meta": np.frombuffer(json.dumps(meta).encode("utf-8"),
                                  np.uint8),
        }
        if extra_rids:
            # per-request conditioning (enc-dec encoder frames) rides in
            # the array section; meta["extra_rids"] names the owners
            state["extra"] = {
                f"r{rid:08d}": np.asarray(self._req[rid].extra, np.float32)
                for rid in extra_rids}
        path = save_checkpoint(self.snapshot_dir, self._step_count, state)
        self.stats.snapshots += 1
        return path

    def restore(self, step: Optional[int] = None) -> int:
        """Load a snapshot into THIS engine (which must be fresh and idle —
        the replacement for a dead one, built with the same configuration)
        and resume serving exactly where the snapshot left off. Defaults to
        the latest snapshot in ``snapshot_dir``. Deadlines resume with the
        remaining budget they had at snapshot time. Returns the restored
        step count; ``stats.recoveries`` counts successful restores."""
        self._check_alive()
        if self.snapshot_dir is None:
            raise ValueError("restore() needs snapshot_dir")
        if self._active.any() or len(self._sched) or self._req \
                or self._finished:
            raise RuntimeError(
                "restore() needs a fresh idle engine (no queued, active, "
                "or unclaimed requests): build a replacement engine with "
                "the same configuration and restore into that"
            )
        if step is None:
            step = ckpt_latest_step(self.snapshot_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no snapshot found in {self.snapshot_dir}")
        state = restore_checkpoint(self.snapshot_dir, int(step))
        meta = json.loads(bytes(np.asarray(state["meta"])).decode("utf-8"))
        if int(meta.get("version", 0)) != 3:
            raise ValueError(
                f"snapshot at step {step} has format version "
                f"{meta.get('version')!r}; this build reads version 3 "
                f"(tenant-aware scheduler + latency histograms) — "
                f"re-snapshot with the current build")
        fp = self._fingerprint()
        if meta["fingerprint"] != fp:
            raise ValueError(
                f"snapshot fingerprint mismatch: saved "
                f"{meta['fingerprint']} vs this engine {fp} — restore "
                f"needs an identically-configured engine"
            )
        if not meta["requests"] and not meta["finished"]:
            raise ValueError(
                f"snapshot at step {step} is EMPTY (no queued, running, "
                f"or unclaimed requests) — restoring it would resume "
                f"nothing. Snapshot after work is submitted, or restore "
                f"an earlier non-empty step explicitly")
        # rebuild the opaque state tree against the runner's template
        # (structure + dtypes — the checkpoint round-trips bf16 through
        # f32 files); leaf-count mismatches raise with the family named
        self.cache = unflatten_state_tree(
            self.runner.init_state(self.batch), state["cache"])
        self._step_count = int(meta["step_count"])
        self._next_rid = int(meta["next_rid"])
        self._clock = int(meta["prefix_clock"])
        self._req = {
            int(rid): Request(
                prompt=np.asarray(d["prompt"], np.int32),
                max_new=int(d["max_new"]),
                stop_tokens=tuple(d["stop_tokens"]),
                sampling=SamplingParams(
                    temperature=float(d["sampling"]["temperature"]),
                    top_k=int(d["sampling"]["top_k"]),
                    seed=int(d["sampling"]["seed"])),
                deadline_ms=d["deadline_ms"],
                tenant=d.get("tenant", "default"),
            ) for rid, d in meta["requests"]}
        for rid in meta.get("extra_rids", []):
            self._req[int(rid)].extra = np.asarray(
                state["extra"][f"r{int(rid):08d}"], np.float32)
        self._out = {int(rid): [int(t) for t in toks]
                     for rid, toks in meta["out"]}
        self._finished, self._status, self._error = {}, {}, {}
        for rid, toks, status, err in meta["finished"]:
            self._finished[int(rid)] = [int(t) for t in toks]
            self._status[int(rid)] = status
            self._error[int(rid)] = err
        now = self._clock_fn()
        self._deadline = {int(rid): now + float(rem)
                          for rid, rem in meta["deadline_remaining_s"]}
        tm = meta["timing"]
        self._submit_t = {int(rid): now - float(age)
                          for rid, age in tm["submit_age_s"]}
        self._sched = Scheduler(self.policy, max_queue=self.max_queue,
                                shed_policy=self.shed_policy,
                                tenant_weights=self.tenant_weights,
                                retry_hint=self.retry_after_hint)
        self._sched.load_state(meta["sched"])
        self._rid_slot = {int(rid): int(s) for rid, s in meta["rid_slot"]}
        sl = meta["slots"]
        self._active = np.asarray(sl["active"], bool)
        self._slot_req = [None if x is None else int(x) for x in sl["req"]]
        self._slot_pos = np.asarray(sl["pos"], np.int32)
        self._slot_last = np.asarray(sl["last"], np.int32)
        self._slot_left = np.asarray(sl["left"], np.int64)
        self._slot_touch = np.asarray(sl["touch"], np.int64)
        self._slot_prompt = [None if p is None else np.asarray(p, np.int32)
                             for p in sl["prompt"]]
        self._slot_rng = []
        for st in sl["rng"]:
            if st is None:
                self._slot_rng.append(None)
            else:
                g = np.random.default_rng(0)
                g.bit_generator.state = st
                self._slot_rng.append(g)
        self._slot_refs = np.zeros(self.batch, np.int64)
        self._prefix_index = OrderedDict(
            ((int(m), bytes.fromhex(raw)), int(slot))
            for m, raw, slot in meta["prefix_index"])
        st = meta["stats"]
        for f in self._STAT_FIELDS:
            setattr(self.stats, f, int(st.get(f, 0)))
        self.stats.prefill_shapes = {
            (int(b), int(s)) for b, s in meta["stats_shapes"]["prefill"]}
        self.stats.decode_shapes = {
            int(b) for b in meta["stats_shapes"]["decode"]}
        # older snapshots also carry an inter-token histogram ("tok") and
        # last-token ages; nothing reads them any more
        self.stats.ttft_ms = LatencyHistogram(meta["stats_hists"]["ttft"])
        self.stats.tenants = {}
        for t, d in meta["stats_tenants"]:
            ts = self.stats.tenant(t)
            ts.submitted = int(d["submitted"])
            ts.admitted = int(d["admitted"])
            ts.completed = int(d["completed"])
            ts.rejected = int(d["rejected"])
            ts.expired = int(d["expired"])
            ts.cancelled = int(d["cancelled"])
            ts.aborted = int(d["aborted"])
            ts.tokens = int(d["tokens"])
            ts.ttft_ms = LatencyHistogram(d["ttft"])
        self.stats.recoveries += 1
        return int(step)


# ---------------------------------------------------------------------------
# The wave baseline (pre-continuous-batching behavior)
# ---------------------------------------------------------------------------


class WaveEngine:
    """Fixed-wave batching baseline: requests are served in waves of
    ``batch``; every wave re-pads to its longest prompt (one recompile per
    distinct wave length) and every slot stalls until the wave's largest
    ``max_new`` finishes. Greedy only.

    Kept as the comparison point for ``benchmarks/serve_bench.py`` and the
    engine-equivalence tests. Shares the masked-padding convention with
    :class:`ServeEngine` (negative pad positions), so its greedy outputs are
    bit-identical to the continuous engine — the old implementation let pad
    tokens leak into attention, which this fixes.
    """

    def __init__(self, model, cfg: ModelConfig, params, batch: int,
                 cache_len: int, *, quantize: str = "off"):
        if cfg.family == "encdec":
            raise ValueError(
                "WaveEngine is a decoder-LM baseline: enc-dec serving "
                "needs a per-request encoder pass — use ServeEngine, "
                "which serves encdec configs through EncDecRunner")
        mix = recurrent_mixer_names(cfg)
        if int(batch) > 1 and mix:
            # a wave of one never pads; larger waves pad to the wave max,
            # and the wave path ships no MoE no-drop dispatch either —
            # batched hybrids belong on ServeEngine's RecurrentRunner
            raise ValueError(
                f"wave prefill left-pads prompts, and the wave baseline "
                f"gives {'/'.join(mix)} layers no pad-validity guarantee "
                f"for their recurrent state — serve this family with "
                f"ServeEngine (pad-aware bucketed prefill) or batch=1 "
                f"waves (never padded)")
        from repro.kernels.block_circulant.plan import (_check_quantize,
                                                        freeze_params)
        _check_quantize(quantize)
        if quantize != "off" and not cfg.swm.enabled:
            raise ValueError(
                "quantize applies to frozen circulant tables; this config "
                "has swm disabled")
        if cfg.swm.enabled:
            params = freeze_params(model.specs(), params, quantize=quantize)
        self.quantize = quantize
        self.model, self.cfg, self.params = model, cfg, params
        self.batch, self.cache_len = int(batch), int(cache_len)
        self.stats = EngineStats()
        self._prefill = jax.jit(make_prefill_step(model, cfg))
        self._decode = jax.jit(make_decode_step(model, cfg))

    @property
    def prefill_compiles(self) -> int:
        return int(self._prefill._cache_size())

    @property
    def decode_compiles(self) -> int:
        return int(self._decode._cache_size())

    def frozen_table_bytes(self) -> int:
        """Resident bytes of the frozen frequency tables (scales included)."""
        from repro.kernels.block_circulant.plan import frozen_table_bytes

        return frozen_table_bytes(self.params)

    def generate(self, requests: List[Request]) -> List[List[int]]:
        """Greedy-decode a list of requests in fixed batched waves."""
        for r in requests:
            _validate_request(r, self.cache_len)
            if r.sampling.temperature > 0 or r.stop_tokens:
                raise ValueError(
                    "WaveEngine is a greedy-only baseline: per-request "
                    "sampling and stop tokens need ServeEngine"
                )
            if r.deadline_ms is not None:
                raise ValueError(
                    "WaveEngine has no request lifecycle: deadlines, "
                    "cancellation, and load shedding need ServeEngine"
                )
        results: List[List[int]] = []
        for i in range(0, len(requests), self.batch):
            results.extend(self._run_wave(requests[i: i + self.batch]))
        return results

    def _run_wave(self, wave: List[Request]) -> List[List[int]]:
        B = self.batch
        plen = max(r.prompt_len for r in wave)
        toks = np.zeros((B, plen), np.int32)
        pos = np.zeros((B, plen), np.int32)
        lens = np.zeros(B, np.int32)
        for j in range(B):
            L = wave[j].prompt_len if j < len(wave) else 0
            lens[j] = L
            if L:
                toks[j, plen - L:] = np.asarray(
                    wave[j].prompt, np.int32).reshape(-1)
            pos[j] = np.arange(plen, dtype=np.int32) - (plen - L)
        cache = self.model.init_cache(B, self.cache_len)
        logits, cache = self._prefill(
            self.params, jnp.asarray(toks), cache, None, jnp.asarray(pos)
        )
        self.stats.prefill_calls += 1
        self.stats.prefill_shapes.add((B, plen))
        outs: List[List[int]] = [[] for _ in wave]
        cur = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
        for j, r in enumerate(wave):
            outs[j].append(int(cur[j]))
            self.stats.tokens_generated += 1
        max_new = max(r.max_new for r in wave)
        for t in range(max_new - 1):
            logits, cache = self._decode(
                self.params, jnp.asarray(cur[:, None]), cache,
                jnp.asarray(lens + t),
            )
            self.stats.decode_steps += 1
            self.stats.slot_steps_active += sum(
                1 for r in wave if t + 1 < r.max_new)
            self.stats.decode_rows += B
            self.stats.decode_shapes.add(B)
            cur = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
            for j, r in enumerate(wave):
                if t + 1 < r.max_new:
                    outs[j].append(int(cur[j]))
                    self.stats.tokens_generated += 1
        for _ in wave:
            self.stats.requests_completed += 1
        return outs
