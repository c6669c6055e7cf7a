"""Model runner: jitted device dispatch for the serving engine.

The bottom layer of the engine (scheduler -> block manager -> runner).
It owns everything that touches the device: the paged KV state, the
device mirror of the block tables AND of the per-slot sampling configs,
the jitted prefill / decode / verify / block-copy callables, and
sampling. It knows nothing about queues, refcounts, or request
lifecycle — the scheduler hands it fully-resolved work (token rows,
table rows, slot ids, SamplingParams) and gets tokens back.

Bucketed batched prefill: queued prompts are padded to a small set of
power-of-two suffix-length buckets and dispatched several at a time
through `lm.prefill_paged` (batch width is also bucketed to powers of
two, padded with inert rows that write only the null block). One jitted
instance serves every batch with the same (width, length) bucket, so
the number of prefill compilations is bounded by
len(width_buckets) * len(length_buckets) — not by the number of
distinct prompt lengths in the workload. `prefill_shapes` records the
distinct compiled shapes so benchmarks can assert the bound.

Bucketed verify (speculative decoding): draft chains are padded to a
small grid of chain-length buckets (`verify_buckets`, powers of two up
to speculate+1) and dispatched through `lm.decode_verify_paged` — the
same trick, so verify compilations are bounded by the bucket grid, not
by the per-step draft lengths. `verify()` returns the emitted token and
accept count at every chain position (greedy compare or Leviathan
accept/reject — see serving/sampling.py); `commit()` then restores each
lane's recurrent state at its accepted length (attention needs no
commit — stale K/V past the accepted point is position-masked until
overwritten).

Per-request sampling configs are DATA: temperature / top-k / top-p /
seed ride through every dispatch as (num_slots,) arrays (mirroring the
block tables), so one compiled instance per shape bucket serves every
mix of configs, and the compile count never depends on how many
distinct SamplingParams a workload carries. Each bucket has at most
FOUR traces — {argmax fast path, full sampler} x {with, without the
top-`max_logprobs` alternative-logprob side output} — so the bound is
4x the bucket grid (2x while no request asks for logprobs). Randomness
is
position-keyed per request (fold_in(PRNGKey(seed), pos)); the runner
holds no sampler state at all, which is what makes a request's stream
independent of batch composition.

All jitted state is donated, so pools update in place: every program
takes and returns the paged pools in the one row form of
`models/attention.py`, and the layer loop carries them, so no dispatch
relayouts or copies a pool. The weights are an argument (not donated)
of every program that reads them, never a closed-over constant, so each
compiled variant shares the one device copy. The bucket-grid helpers live
in `serving/bucketing.py` (shared with the bench's shape assertions).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serving import kv_cache, sampling
from repro.serving.block_manager import NULL_BLOCK
from repro.serving.bucketing import (chain_buckets, next_pow2,  # noqa: F401
                                     normalize_buckets, pick_bucket,
                                     pow2_buckets, width_buckets)
from repro.serving.observability import (NULL_OBS, Observability, span,
                                         spanned)
from repro.serving.sampling import GREEDY, SamplingParams

RECURRENT_KINDS = ("rwkv", "rec")

# default chunked-prefill budget (tokens per prefill dispatch); prompts
# whose suffix exceeds the largest prefill bucket are split into chunks
# of at most this size — see ModelRunner(prefill_chunk=...)
DEFAULT_PREFILL_CHUNK = 2048


@dataclasses.dataclass
class PrefillRow:
    """One sequence of a prefill batch, fully resolved by the scheduler:
    suffix tokens to compute, how much of the prompt is cache-hit, the
    request's sampling config, and where the results land."""
    tokens: np.ndarray          # (P,) the FULL prompt, int32
    cached_len: int             # prompt tokens already present in blocks
    slot: int                   # decode lane (recurrent state index)
    table_row: np.ndarray       # (max_blocks,) int32, NULL padded
    sampling: SamplingParams = GREEDY

    @property
    def start(self) -> int:     # first computed position
        return min(self.cached_len, len(self.tokens) - 1)

    @property
    def suffix_len(self) -> int:
        return len(self.tokens) - self.start


def _map_routed(state, fn):
    """The paged state with fn applied to every MoE layer's expert
    counters (their "routed" leaves); the same state without MoE."""
    def one(st):
        if isinstance(st, dict) and "routed" in st:
            return {**st, "routed": fn(st["routed"])}
        return st
    return {"prefix": [one(st) for st in state["prefix"]],
            "blocks": {k: one(st) for k, st in state["blocks"].items()}}


class ModelRunner:
    """Owns device state + jitted dispatch. See module docstring."""

    def __init__(self, params, cfg: ModelConfig, *, num_slots: int,
                 block_size: int, num_blocks: int, max_blocks_per_seq: int,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 prefill_max_batch: int = 4,
                 prefill_chunk: Optional[int] = None, speculate: int = 0,
                 max_logprobs: int = 8, kv_dtype: str = "fp16",
                 obs: Observability = NULL_OBS,
                 now_fn: Optional[Callable[[], float]] = None):
        if kv_dtype not in kv_cache.KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; expected "
                             f"{kv_cache.KV_DTYPES}")
        self.cfg = cfg
        self._obs = obs or NULL_OBS
        self._now = now_fn or (lambda: 0.0)
        # dispatch counters resolved once (no-ops when obs is off)
        self._c_prefill = self._obs.counter("prefill_dispatches_total")
        self._c_decode = self._obs.counter("decode_dispatches_total")
        self._c_verify = self._obs.counter("verify_dispatches_total")
        self._c_copies = self._obs.counter("block_copies_total")
        # compiled-variant sets: a dispatch whose (bucket, static args)
        # combination is unseen triggers a jit compile — the trace flags
        # it `first_dispatch` so compile stalls are attributable
        self._prefill_variants: set = set()
        self._decode_variants: set = set()
        self._verify_variants: set = set()
        self.num_slots = num_slots
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.kv_dtype = kv_dtype
        self.state = kv_cache.init_paged_state(cfg, num_slots, num_blocks,
                                               block_size, kv_dtype)
        self.cache_bytes = kv_cache.paged_bytes(cfg, num_blocks, block_size,
                                                kv_dtype)
        self.block_bytes = kv_cache.block_bytes(cfg, block_size, kv_dtype)
        self._has_recurrent = any(
            k in RECURRENT_KINDS
            for k in cfg.block_pattern + cfg.prefix_pattern)

        max_len = max_blocks_per_seq * block_size
        self.prefill_buckets = normalize_buckets(
            prefill_buckets, max_len, start=min(16, next_pow2(max_len)))
        # chunked prefill budget: suffixes longer than the largest
        # prefill bucket are split by the scheduler into chunks of at
        # most this many tokens, dispatched across successive steps.
        # None = auto (DEFAULT_PREFILL_CHUNK, capped to the grid — a
        # no-op for short-context configs whose grid already covers
        # max_len); 0 = disabled (oversized suffixes are rejected with
        # an actionable error instead of compiling an oversized
        # variant). The budget is bucket-aligned, and the bucket grid
        # is capped at it so no dispatch ever exceeds the budget.
        if prefill_chunk is None:
            prefill_chunk = DEFAULT_PREFILL_CHUNK
        if prefill_chunk:
            budget = pick_bucket(min(prefill_chunk, max_len),
                                 self.prefill_buckets)
            self.prefill_buckets = [b for b in self.prefill_buckets
                                    if b <= budget]
            self.prefill_chunk = budget
        else:
            self.prefill_chunk = 0
            if prefill_buckets:
                # chunking explicitly off + an explicit grid: the grid
                # is a hard cap (no silent extension to max_len), so an
                # oversized suffix raises the actionable suffix_bucket
                # error instead of compiling an unbounded variant
                self.prefill_buckets = sorted(
                    set(int(b) for b in prefill_buckets))
        self.prefill_max_batch = max(1, prefill_max_batch)
        self.width_buckets = width_buckets(self.prefill_max_batch)
        self.speculate = max(0, speculate)
        self.verify_buckets = chain_buckets(self.speculate)

        # host tables + device mirror (refreshed lazily when dirty)
        self._tables = np.zeros((num_slots, max_blocks_per_seq), np.int32)
        self._tables_dev = jnp.asarray(self._tables)
        self._tables_dirty = False

        # per-slot sampling configs, the same pattern as the tables:
        # host arrays of plain data, mirrored to the device lazily
        self._temps = np.zeros(num_slots, np.float32)
        self._topks = np.zeros(num_slots, np.int32)
        self._topps = np.ones(num_slots, np.float32)
        self._seeds = np.zeros(num_slots, np.int32)
        self._wantk = np.zeros(num_slots, np.int32)   # requested logprob k
        self._sampling_dev = None
        # static top-k width of the alternative-logprob side output (one
        # compiled width serves every per-request k <= max_logprobs; the
        # scheduler slices each request's k columns host-side)
        self.max_logprobs = max(1, min(max_logprobs, cfg.vocab_size))

        # telemetry; *_shapes are process-cumulative (compilations
        # persist across runs), the counters are reset per run
        self.prefill_shapes: set = set()     # distinct (width, Ls) dispatched
        self.verify_shapes: set = set()      # distinct chain buckets T
        self._snaps = None                   # pending recurrent snapshots
        self.reset_stats()

        K = self.max_logprobs
        bs = block_size
        self.params = params

        def _decode(params, state, tokens, positions, tables, temps, topks,
                    topps, seeds, do_sample, want_alt):
            logits, state = lm.decode_step_paged(params, cfg, state, tokens,
                                                 positions, tables, bs)
            if do_sample:
                tok, lp = sampling.sample_tokens(logits, positions, temps,
                                                 topks, topps, seeds)
            else:
                tok, lp = sampling.greedy_tokens(logits)
            alt = sampling.top_alternatives(logits, K) if want_alt else None
            return tok, lp, alt, state

        self._decode_fn = jax.jit(_decode, donate_argnums=(1,),
                                  static_argnums=(9, 10))

        def _verify(params, state, tokens, positions, counts, tables, temps,
                    topks, topps, seeds, do_sample, want_alt):
            logits, state, snaps = lm.decode_verify_paged(
                params, cfg, state, tokens, positions, counts, tables, bs)
            if do_sample:
                emit, accept, lp = sampling.verify_tokens(
                    logits, tokens, counts, positions, temps, topks, topps,
                    seeds)
            else:
                emit, accept, lp = sampling.greedy_verify_tokens(
                    logits, tokens, counts)
            alt = sampling.top_alternatives(logits, K) if want_alt else None
            return emit, accept, lp, alt, state, snaps

        self._verify_fn = jax.jit(_verify, donate_argnums=(1,),
                                  static_argnums=(10, 11))

        def _commit(state, snaps, idx):
            return lm.commit_decode_state(cfg, state, snaps, idx)

        self._commit_fn = jax.jit(_commit, donate_argnums=(0,))

        def _prefill(params, state, toks, lengths, cached, rows, slots,
                     resume):
            return lm.prefill_paged(params, cfg, state, toks, lengths,
                                    cached, rows, slots, bs, resume=resume)

        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,),
                                   static_argnums=(7,))

        def _first(last, positions, temps, topks, topps, seeds, do_sample,
                   want_alt):
            if do_sample:
                tok, lp = sampling.sample_tokens(last, positions, temps,
                                                 topks, topps, seeds)
            else:
                tok, lp = sampling.greedy_tokens(last)
            alt = sampling.top_alternatives(last, K) if want_alt else None
            return tok, lp, alt

        self._first_fn = jax.jit(_first, static_argnums=(6, 7))

        def _copy(state, src, dst):
            return kv_cache.copy_block(cfg, state, src, dst, bs)

        self._copy_fn = jax.jit(_copy, donate_argnums=(0,))

        # host-tier payload movement: a single-block jitted gather
        # (demotion) and a width-bucketed jitted scatter (revival).
        # Promotion batches pad to `promote_buckets` via pick_bucket, so
        # revivals never compile outside the bucket grid
        # (`promote_shapes` records dispatched widths for the bound
        # assertion, like prefill_shapes).
        self.promote_buckets = pow2_buckets(max_blocks_per_seq)
        self.promote_shapes: set = set()

        def _gather(state, ids):
            return kv_cache.gather_blocks(cfg, state, ids, bs)

        self._gather_fn = jax.jit(_gather)

        def _upload(state, ids, payload):
            return kv_cache.scatter_blocks(cfg, state, ids, payload, bs)

        self._upload_fn = jax.jit(_upload, donate_argnums=(0,))

    def reset_stats(self) -> None:
        self.state = _map_routed(self.state, jnp.zeros_like)
        self.prefill_dispatches = 0
        self.prefill_padded_tokens = 0       # token slots incl. padding
        self.prefill_computed_tokens = 0     # true suffix tokens computed
        self.prefill_peak_score_bytes = 0    # analytic peak f32 score tile
        self.block_copies = 0
        self.verify_dispatches = 0
        self.verify_padded_tokens = 0        # chain slots incl. padding
        self.verify_chain_tokens = 0         # true chain tokens verified
        self.sampled_dispatches = 0          # decode/verify full-sampler uses

    def routed_counts(self) -> Optional[np.ndarray]:
        """Routed-token counts per MoE layer and expert, (n_moe_layers,
        E) in layer order, accumulated on the device by every prefill,
        decode and verify dispatch since the last reset_stats: one
        device read, made only when asked. None without MoE layers."""
        rows = [st["routed"][None] for st in self.state["prefix"]
                if "routed" in st]
        stacked = [st["routed"] for st in self.state["blocks"].values()
                   if "routed" in st]
        if stacked:       # superblock-major: layer j of every position
            rows.append(jnp.stack(stacked, 1).reshape(-1,
                                                      self.cfg.n_experts))
        if not rows:
            return None
        return np.asarray(jnp.concatenate(rows))

    # ------------------------------------------------------------------
    # block tables
    # ------------------------------------------------------------------

    def write_table(self, slot: int, row: np.ndarray) -> None:
        self._tables[slot] = row
        self._tables_dirty = True

    def clear_table(self, slot: int) -> None:
        self._tables[slot] = NULL_BLOCK
        self._tables_dirty = True
        self.clear_sampling(slot)

    def _tables_device(self):
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self._tables)
            self._tables_dirty = False
        return self._tables_dev

    # ------------------------------------------------------------------
    # per-slot sampling configs
    # ------------------------------------------------------------------

    def set_sampling(self, slot: int, sp: SamplingParams) -> None:
        self._temps[slot] = sp.temperature
        self._topks[slot] = sp.top_k
        self._topps[slot] = sp.top_p
        self._seeds[slot] = sampling.seed32(sp.seed)
        self._wantk[slot] = min(sp.logprobs, self.max_logprobs)
        self._sampling_dev = None

    def clear_sampling(self, slot: int) -> None:
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._topps[slot] = 1.0
        self._seeds[slot] = 0
        self._wantk[slot] = 0
        self._sampling_dev = None

    @property
    def any_sampled(self) -> bool:
        """True while any live slot samples (temperature > 0) — selects
        the full-sampler trace over the argmax fast path."""
        return bool(self._temps.max() > 0.0)

    @property
    def any_alt(self) -> bool:
        """True while any live slot asked for alternative logprobs —
        selects the trace with the top-k side output."""
        return bool(self._wantk.max() > 0)

    def _sampling_device(self):
        if self._sampling_dev is None:
            self._sampling_dev = (jnp.asarray(self._temps),
                                  jnp.asarray(self._topks),
                                  jnp.asarray(self._topps),
                                  jnp.asarray(self._seeds))
        return self._sampling_dev

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def suffix_bucket(self, n: int) -> int:
        """Smallest configured length bucket covering n suffix tokens.

        A suffix that no bucket covers would otherwise fall through to
        an oversized jit variant (the full dense score tensor) — the
        scheduler must route it to chunked admission first, so reaching
        here oversized is an error, with the fix spelled out."""
        if n > self.prefill_buckets[-1]:
            raise ValueError(
                f"prompt suffix of {n} tokens exceeds the largest "
                f"prefill bucket ({self.prefill_buckets[-1]}) and "
                f"chunked prefill is disabled (prefill_chunk=0); enable "
                f"chunked admission (prefill_chunk > 0, serve.py "
                f"--prefill-chunk) or widen --prefill-buckets")
        return pick_bucket(n, self.prefill_buckets)

    def chain_bucket(self, n: int) -> int:
        """Smallest verify bucket covering an n-token draft chain."""
        return pick_bucket(n, self.verify_buckets)

    @spanned("serve.runner.prefill")
    def prefill(self, rows: List[PrefillRow], *, resume: bool = False,
                chunk: Optional[Tuple[int, int]] = None):
        """Run one bucketed batched prefill and sample each row's first
        token from its true-last-position logits with the row's own
        SamplingParams (position-keyed on the last prompt position).
        Blocks until done (the caller's TTFT clock covers it). Returns
        ((len(rows),) int32 tokens, (len(rows),) float32 logprobs,
        alt) where alt is None unless a row asked for logprobs — then
        ((len(rows), max_logprobs) int32 ids, (..., max_logprobs)
        float32 logprobs) of the top alternatives at each row's last
        prompt position.

        resume=True marks a chunked-prefill continuation (chunk >= 1 of
        a split admission): recurrent layers pick their scanned state up
        from the slot where the previous chunk left it — a separate jit
        trace, so it rides in the dispatch-variant key. `chunk` is
        (index, total) of the admission's chunk sequence, recorded on
        the prefill step trace so Perfetto can attribute TTFT across a
        multi-chunk admission; for non-final chunks the sampled "first
        token" is a mid-prompt artifact the scheduler discards."""
        n = len(rows)
        obs = self._obs
        t0 = self._now() if obs.enabled else 0.0
        with span("serve.runner.prefill.stage"):
            ls = self.suffix_bucket(max(r.suffix_len for r in rows))
            width = pick_bucket(n, self.width_buckets)
            toks = np.zeros((width, ls), np.int32)
            lengths = np.zeros(width, np.int32)
            cached = np.zeros(width, np.int32)
            tables = np.full((width, self.max_blocks_per_seq), NULL_BLOCK,
                             np.int32)
            slots = np.full(width, self.num_slots, np.int32)  # pad rows drop
            temps = np.zeros(width, np.float32)
            topks = np.zeros(width, np.int32)
            topps = np.ones(width, np.float32)
            seeds = np.zeros(width, np.int32)
            for i, r in enumerate(rows):
                suf = r.tokens[r.start:]
                toks[i, :len(suf)] = suf
                lengths[i] = len(r.tokens)
                cached[i] = r.cached_len
                tables[i] = r.table_row
                slots[i] = r.slot
                temps[i] = r.sampling.temperature
                topks[i] = r.sampling.top_k
                topps[i] = r.sampling.top_p
                seeds[i] = sampling.seed32(r.sampling.seed)
            self.prefill_shapes.add((width, ls))
            self.prefill_dispatches += 1
            self.prefill_padded_tokens += width * ls
            self.prefill_computed_tokens += sum(r.suffix_len for r in rows)
            # analytic peak attention-score bytes for this dispatch: the
            # streamed path (attention.streamed_paged_attention) bounds
            # the pool band at attn_chunk keys, plus the (ls, ls) suffix
            # tile — f32 scores per head. Benchmarks assert this stays
            # flat as the prompt grows past the chunk budget.
            kv_band = min(self.max_blocks_per_seq * self.block_size,
                          self.cfg.attn_chunk)
            score_bytes = (4 * width * self.cfg.n_heads * ls
                           * (kv_band + ls))
            self.prefill_peak_score_bytes = max(
                self.prefill_peak_score_bytes, score_bytes)
            do_sample = bool(temps.max() > 0.0)
            want_alt = any(r.sampling.logprobs for r in rows)
            args = [jnp.asarray(a)
                    for a in (toks, lengths, cached, tables, slots)]
            first_args = [jnp.asarray(a) for a in (
                np.maximum(lengths - 1, 0), temps, topks, topps, seeds)]
        last, self.state = self._prefill_fn(self.params, self.state, *args,
                                            resume)
        first, lp, alt = self._first_fn(last, *first_args, do_sample,
                                        want_alt)
        with span("serve.runner.prefill.fetch"):
            out = (np.asarray(first, np.int32)[:n],
                   np.asarray(lp, np.float32)[:n], self._host_alt(alt, n))
        if obs.enabled:
            variant = (width, ls, do_sample, want_alt, resume)
            self._c_prefill.inc()
            extra = {}
            if chunk is not None:
                extra = {"chunk": chunk[0], "chunks_total": chunk[1]}
            obs.step("prefill", t0, self._now(), batch=n,
                     bucket=[width, ls],
                     first_dispatch=variant not in self._prefill_variants,
                     emitted=n,
                     computed_tokens=sum(r.suffix_len for r in rows),
                     padded_tokens=width * ls,
                     cached_tokens=sum(r.start for r in rows), **extra)
            self._prefill_variants.add(variant)
        return out

    @staticmethod
    def _host_alt(alt, n: Optional[int] = None):
        if alt is None:
            return None
        ids, lps = alt
        ids = np.asarray(ids, np.int32)
        lps = np.asarray(lps, np.float32)
        return (ids[:n], lps[:n]) if n is not None else (ids, lps)

    @spanned("serve.runner.decode")
    def decode(self, tokens: np.ndarray, positions: np.ndarray):
        """One batched decode step over all lanes. tokens/positions:
        (num_slots,) int32 host arrays. Returns ((num_slots,) int32
        next tokens, (num_slots,) float32 chosen logprobs, alt — None
        or the top-max_logprobs ((num_slots, K) ids, (num_slots, K)
        logprobs) when any live slot asked for alternatives)."""
        obs = self._obs
        t0 = self._now() if obs.enabled else 0.0
        with span("serve.runner.decode.stage"):
            do_sample = self.any_sampled
            if do_sample:
                self.sampled_dispatches += 1
            want_alt = self.any_alt
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    self._tables_device(), *self._sampling_device())
        next_tok, lp, alt, self.state = self._decode_fn(
            self.params, self.state, *args, do_sample, want_alt)
        with span("serve.runner.decode.fetch"):
            out = np.asarray(next_tok), np.asarray(lp), self._host_alt(alt)
        if obs.enabled:
            variant = (do_sample, want_alt)
            self._c_decode.inc()
            obs.step("decode", t0, self._now(), batch=self.num_slots,
                     first_dispatch=variant not in self._decode_variants,
                     sampled=do_sample)
            self._decode_variants.add(variant)
        return out

    @spanned("serve.runner.verify")
    def verify(self, tokens: np.ndarray, positions: np.ndarray,
               counts: np.ndarray):
        """One batched multi-token verify dispatch. tokens: (num_slots,
        T) draft chains right-padded to a verify bucket; positions /
        counts: (num_slots,) int32 (counts 0 = lane sits out). Returns
        (emitted tokens (num_slots, T) int32 — valid at chain indices
        0..accept —, accept counts (num_slots,) int32, chosen logprobs
        (num_slots, T) float32, alt — None or the per-position
        top-max_logprobs ((num_slots, T, K) ids, (num_slots, T, K)
        logprobs)). Greedy lanes emit the model argmax at
        every position (accept = longest agreeing draft prefix, exactly
        the bit-identity rule); sampled lanes run Leviathan
        accept/reject with residual resampling (serving/sampling.py).
        Recurrent snapshots are held until the matching `commit`."""
        T = tokens.shape[1]
        obs = self._obs
        t0 = self._now() if obs.enabled else 0.0
        self.verify_shapes.add(T)
        self.verify_dispatches += 1
        self.verify_padded_tokens += tokens.shape[0] * T
        self.verify_chain_tokens += int(counts.sum())
        with span("serve.runner.verify.stage"):
            do_sample = self.any_sampled
            if do_sample:
                self.sampled_dispatches += 1
            want_alt = self.any_alt
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(counts), self._tables_device(),
                    *self._sampling_device())
        emit, accept, lp, alt, self.state, self._snaps = self._verify_fn(
            self.params, self.state, *args, do_sample, want_alt)
        with span("serve.runner.verify.fetch"):
            out = (np.asarray(emit), np.asarray(accept), np.asarray(lp),
                   self._host_alt(alt))
        if obs.enabled:
            variant = (T, do_sample, want_alt)
            self._c_verify.inc()
            obs.step("verify", t0, self._now(), batch=tokens.shape[0],
                     bucket=T,
                     first_dispatch=variant not in self._verify_variants,
                     chain_tokens=int(counts.sum()),
                     padded_tokens=tokens.shape[0] * T,
                     sampled=do_sample)
            self._verify_variants.add(variant)
        return out

    def commit(self, idx: np.ndarray) -> None:
        """Commit per-lane recurrent state at `idx` accepted chain
        tokens (0 = keep the pre-verify state). Must follow every
        `verify`; a no-op for pure-attention architectures, whose
        rollback is entirely positional."""
        if self._has_recurrent and self._snaps is not None:
            self.state = self._commit_fn(self.state, self._snaps,
                                         jnp.asarray(idx))
        self._snaps = None

    @spanned("serve.runner.copy_block")
    def copy_block(self, src: int, dst: int) -> None:
        """Device-side copy-on-write: clone block `src`'s K/V into `dst`
        in every attention pool."""
        self.state = self._copy_fn(self.state, jnp.int32(src),
                                   jnp.int32(dst))
        self.block_copies += 1
        self._c_copies.inc()

    # ------------------------------------------------------------------
    # host-tier payload movement (BlockAllocator demotion / revival)
    # ------------------------------------------------------------------

    def fetch_block(self, block: int):
        """Device -> host: one block's payload from every attention pool
        (a kv_cache.gather_blocks tree of (1, ...) numpy leaves;
        quantized pools include the scale tables verbatim) — the
        allocator's host-tier demotion callback."""
        payload = self._gather_fn(self.state,
                                  jnp.asarray([block], jnp.int32))
        return jax.device_get(payload)

    def upload_blocks(self, ids: Sequence[int], payloads: Sequence) -> None:
        """Host -> device: scatter demoted payloads back into the pools
        at `ids` (the allocator's revival callback). The batch pads to a
        promote_buckets width — pad lanes target the reserved null
        block — so one jitted scatter per bucket width serves every
        revival."""
        n = len(ids)
        w = pick_bucket(n, self.promote_buckets)
        self.promote_shapes.add(w)
        idarr = np.full(w, NULL_BLOCK, np.int32)
        idarr[:n] = ids

        def cat(*leaves):
            out = np.concatenate(leaves, axis=0)
            if w > n:
                pad = np.zeros((w - n,) + out.shape[1:], out.dtype)
                out = np.concatenate([out, pad], axis=0)
            return out

        payload = jax.tree.map(cat, payloads[0], *payloads[1:])
        self.state = self._upload_fn(self.state, jnp.asarray(idarr),
                                     payload)
