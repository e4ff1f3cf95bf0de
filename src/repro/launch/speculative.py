"""Speculative decoding through the ragged engine step.

A DRAFTER proposes up to k candidate tokens per decoding slot; the engine
feeds ``[last_token, d_1 .. d_k]`` through the SAME ragged multi-token step
chunked prefill uses (`launch.steps.build_engine_step(speculate_k=k)`), so
ONE pass of the AMS-quantized weights + KV pool scores every candidate.
The step returns target logits at all k+1 fed positions; this module's
`verify_tokens` then decides, on device, the longest accepted draft prefix
and the one extra token every round emits (the "bonus" draw when all
drafts are accepted, the corrective draw at the first rejection).

Acceptance rule (the standard rejection scheme, specialized to
DETERMINISTIC drafters — both built-in drafters propose point masses):

  * greedy rows (temperature == 0): draft j+1 is accepted iff it equals
    ``argmax`` of the target logits at position j; the emitted extra token
    is the argmax at the first mismatch (or after the last draft). The
    emitted stream is therefore BIT-IDENTICAL to non-speculative greedy
    decoding — speculation only changes how many tokens emerge per step,
    never which tokens.
  * sampled rows (temperature > 0): with a deterministic proposal q =
    delta(d_j), draft j is accepted with probability p_j(d_j) where p_j is
    the target distribution (temperature / top-k / top-p transforms of
    `launch.sampling`, applied to the logits at position j). On rejection
    the extra token is drawn from the residual ``norm(max(p_j - q, 0))``,
    which for a point-mass q is exactly p_j with d_j masked out and
    renormalized. A round where every draft is accepted draws the bonus
    token from p_k unmodified. Each emitted position therefore marginally
    follows the exact target distribution (`tests/test_speculative.py`
    pins this with a chi-square test).

PRNG discipline matches `launch.sampling`: the key for the decision at
stream index n is ``fold_in(request_key, n)`` — request id + token index,
never the slot, tick, or round shape — with the accept uniform and the
resample draw split off that key by a further fold. Seeded speculative
streams replay bit-identically across restarts, slot counts and chunk
settings (though not across drafters: different proposals consume the
acceptance uniforms differently at temperature > 0).

Termination (stop tokens / length cap, PR 5) is applied in-step per
EMITTED index: the round's emission is truncated at the first stop-token
hit or at the length cap, so a stop token can land mid-round.

Rollback of rejected KV entries happens in the same jitted program (see
`truncate_cache` here and `pool.paged_truncate`): rejected suffix
positions are zero-scattered back to the pool's initial state, so a later
re-insert at those positions is indistinguishable from a straight insert
(quantization at insert is deterministic). The engine then rewinds its
host-side feed position — never past the shared prefix-cache pages, which
speculation structurally cannot touch (drafting starts after the prompt).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sampling import _masked_logits


# ---------------------------------------------------------------------------
# drafters (host-side proposal; both deterministic)
# ---------------------------------------------------------------------------
class Drafter:
    """Proposal interface: ``propose(history, k)`` returns up to k draft
    tokens (np.int32 [<=k]) continuing ``history`` (prompt + generated so
    far, [L] int32). Proposals must be DETERMINISTIC functions of the
    history — the rejection rule implemented here assumes point-mass
    proposals, and replay determinism of seeded streams depends on it."""

    name = "drafter"
    _m_proposed = None        # per-drafter proposal counter (bind_metrics)

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        raise NotImplementedError

    def bind_metrics(self, registry) -> None:
        """Attach an `obs.MetricsRegistry`: proposals are counted per
        drafter name, so mixed-drafter deployments stay attributable.
        The engine binds its registry at construction."""
        self._m_proposed = registry.counter(
            "spec_drafter_proposed_total",
            "draft tokens proposed, by drafter", ("drafter",)
        ).labels(drafter=self.name)

    def record_proposal(self, n: int) -> None:
        """Called by the engine for each accepted-into-the-step proposal
        block (no-op until bind_metrics)."""
        if self._m_proposed is not None:
            self._m_proposed.inc(n)


class NgramDrafter(Drafter):
    """Prompt-lookup decoding: match the longest trailing n-gram of the
    history against its earlier occurrences and propose the tokens that
    followed the MOST RECENT match. Free (no model call) and strong on
    repetitive continuations — retrieval prompts, code, and the looping
    tails greedy decoding produces."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"{min_ngram}..{max_ngram}")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        h = np.asarray(history, np.int32)
        L = h.shape[0]
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            pattern = h[L - n:]
            # candidate start positions of earlier occurrences (the match
            # must END before the trailing n-gram starts)
            starts = np.arange(L - n)
            windows = np.lib.stride_tricks.sliding_window_view(h[:L - 1], n) \
                if L - 1 >= n else np.zeros((0, n), np.int32)
            hits = starts[:windows.shape[0]][
                np.all(windows == pattern[None, :], axis=1)]
            if hits.size:
                p = int(hits[-1])                    # most recent occurrence
                return h[p + n: p + n + k].copy()
        return np.zeros(0, np.int32)


class SelfDrafter(Drafter):
    """Early-exit self-drafting: greedy proposals from the FIRST
    ``draft_groups`` stacked layer groups of the serving model itself —
    the same (quantized) weights, embedding and head, just a truncated
    stack. Zero extra parameters; the draft forward reuses
    `models.forward_seq` over a fixed-capacity buffer (causal masking
    makes the padding inert), compiled once per engine.

    ``draft_groups=None`` keeps the full stack (an exact-oracle drafter,
    useful for tests and accept-rate ceilings)."""

    name = "self"

    def __init__(self, params, cfg, capacity: int, *,
                 draft_groups: Optional[int] = 1, tp: int = 1, policy=None):
        import dataclasses as _dc

        from repro.models import forward_seq
        from repro.models.transformer import layer_pattern

        pat = layer_pattern(cfg)
        n_groups = jax.tree.leaves(params["layers"])[0].shape[0]
        g = n_groups if draft_groups is None else draft_groups
        if not 1 <= g <= n_groups:
            raise ValueError(f"draft_groups must be in [1, {n_groups}], got {g}")
        self.draft_params = {
            "embed": params["embed"],
            "layers": jax.tree.map(lambda x: x[:g], params["layers"]),
            "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }
        # the truncated stack has g full pattern repeats and no tail
        self.draft_cfg = _dc.replace(cfg, num_layers=g * len(pat))
        self.capacity = capacity

        def fwd(p, tokens):
            logits, _, _ = forward_seq(p, tokens, self.draft_cfg, tp=tp,
                                       policy=policy, ctx=None, remat=False)
            return jnp.argmax(logits[0], axis=-1).astype(jnp.int32)  # [S]

        # rounds like the engine step it drafts for (see build_engine_step)
        self._fwd = jax.jit(
            fwd, compiler_options={"xla_allow_excess_precision": False})

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        h = np.asarray(history, np.int32)
        # keep the most recent context that leaves room for k drafts in the
        # fixed buffer (proposals from a truncated context are still valid
        # proposals — correctness lives in the verify step)
        h = h[max(0, h.shape[0] - (self.capacity - k)):]
        L = h.shape[0]
        buf = np.zeros(self.capacity, np.int32)
        buf[:L] = h
        out = []
        for j in range(k):
            nxt = int(np.asarray(self._fwd(self.draft_params,
                                           jnp.asarray(buf[None, :])))[L + j - 1])
            buf[L + j] = nxt
            out.append(nxt)
        return np.asarray(out, np.int32)


def make_drafter(name: str, *, params=None, cfg=None, capacity: int = 0,
                 tp: int = 1, policy=None) -> Drafter:
    """Engine-facing factory: ``"ngram"`` needs nothing; ``"self"`` binds
    the first stacked group of the engine's own params/config, and
    ``"self-full"`` the whole stack (the accept-rate ceiling: proposals
    are the target model's own greedy continuations, re-derived without
    the quantized KV pool)."""
    if name == "ngram":
        return NgramDrafter()
    if name in ("self", "self-full"):
        return SelfDrafter(params, cfg, capacity, tp=tp, policy=policy,
                           draft_groups=None if name == "self-full" else 1)
    raise ValueError(f"unknown drafter {name!r} "
                     "(expected 'ngram', 'self' or 'self-full')")


# ---------------------------------------------------------------------------
# on-device verify: accept / resample / terminate
# ---------------------------------------------------------------------------
def _row_greedy(logits, drafts, ndraft):
    """One slot, temperature 0: accepted = longest draft prefix matching
    the running argmax; candidate token at every position is the argmax."""
    cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)          # [K+1]
    jj = jnp.arange(drafts.shape[0])
    ok = (drafts == cand[:-1]) & (jj < ndraft)
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
    return acc.astype(jnp.int32), cand


def _row_sampled(logits, drafts, ndraft, key, ngen, temperature, top_k, top_p):
    """One slot, temperature > 0: rejection rule against the point-mass
    proposal. Position j's decisions use fold_in(key, ngen + j) — the same
    token-index key discipline as `sampling.sample_tokens` — with the
    accept uniform and the resample draw on distinct sub-folds."""
    K = drafts.shape[0]
    v = logits.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits.astype(jnp.float32) / safe_t                  # [K+1, V]
    masked = jax.vmap(_masked_logits, in_axes=(0, None, None))(
        scaled, top_k, top_p)
    logp = jax.nn.log_softmax(masked, axis=-1)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, ngen + jnp.arange(K + 1))
    k_accept = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, 1)
    k_draw = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, 2)

    # accept draft j with probability p_j(d_j)
    p_d = jnp.exp(jnp.take_along_axis(logp[:K], drafts[:, None], axis=-1)[:, 0])
    u = jax.vmap(jax.random.uniform)(k_accept[:K])
    jj = jnp.arange(K)
    ok = (u < p_d) & (jj < ndraft)
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))

    # candidate at j < ndraft: residual draw = p_j with d_j masked out
    # (point-mass residual); at j >= ndraft: the unmodified bonus draw
    excl = jnp.where(jax.nn.one_hot(drafts, v, dtype=bool), -jnp.inf,
                     masked[:K])
    resampled = jax.vmap(jax.random.categorical)(k_draw[:K], excl)
    plain = jax.vmap(jax.random.categorical)(k_draw, masked)
    cand = jnp.concatenate([jnp.where(jj < ndraft, resampled, plain[:K]),
                            plain[K:]]).astype(jnp.int32)
    return acc.astype(jnp.int32), cand


def verify_tokens(logits, token, nvalid, ndraft, sampling, k_max: int):
    """The speculative step epilogue: accept drafts, emit, terminate.

    logits   [B, K+1, V]  target logits at the last ndraft+1 fed positions
                          (row j scores the token AFTER draft j; row 0 is
                          the position non-speculative decoding samples)
    token    [B, C]       the fed chunk; drafts sit at chunk indices
                          nvalid-ndraft .. nvalid-1
    nvalid   [B]          fed count per slot (1 + ndraft for spec rounds)
    ndraft   [B]          draft count per slot (0 = plain decode/prefill)
    sampling              the `slot_batch` pytree

    Returns (out_tokens [B, K+1], n_emit [B], accepted [B], done [B]):
    ``out_tokens[:, :n_emit]`` are the round's emitted tokens (accepted
    drafts then the bonus/corrective draw, truncated at the first in-step
    stop-token or length-cap hit); ``accepted`` is the accepted-draft
    count (before truncation — the accept-rate statistic). Slots with
    ndraft == 0 reduce exactly to `sampling.sample_tokens` semantics:
    one emitted token, same greedy argmax, same done rule.
    """
    B, C = token.shape
    dstart = nvalid - ndraft                                  # first draft idx
    didx = jnp.clip(dstart[:, None] + jnp.arange(k_max)[None, :], 0, C - 1)
    drafts = jnp.take_along_axis(token, didx, axis=1)         # [B, K]

    def all_greedy_fn(lg):
        return jax.vmap(_row_greedy)(lg, drafts, ndraft)

    def mixed_fn(lg):
        acc_s, cand_s = jax.vmap(_row_sampled)(
            lg, drafts, ndraft, sampling["key"], sampling["ngen"],
            sampling["temperature"], sampling["top_k"], sampling["top_p"])
        acc_g, cand_g = jax.vmap(_row_greedy)(lg, drafts, ndraft)
        sampled = sampling["temperature"] > 0.0
        return (jnp.where(sampled, acc_s, acc_g),
                jnp.where(sampled[:, None], cand_s, cand_g))

    all_greedy = jnp.all(sampling["temperature"] <= 0.0)
    acc, cand = jax.lax.cond(all_greedy, all_greedy_fn, mixed_fn,
                             logits.astype(jnp.float32))

    final = jnp.take_along_axis(cand, acc[:, None], axis=1)[:, 0]
    jj = jnp.arange(k_max + 1)[None, :]
    dpad = jnp.pad(drafts, ((0, 0), (0, 1)))
    out = jnp.where(jj < acc[:, None], dpad,
                    jnp.where(jj == acc[:, None], final[:, None], 0)
                    ).astype(jnp.int32)

    # in-step termination per EMITTED index: stop-token hit or length cap
    # truncates the round's emission (PR 5 semantics, generalized to k+1)
    stop_hit = jnp.any(out[:, :, None] == sampling["stop_ids"][:, None, :],
                       axis=-1)
    len_hit = sampling["ngen"][:, None] + jj + 1 >= \
        sampling["max_tokens"][:, None]
    end = (stop_hit | len_hit) & (jj <= acc[:, None])
    done = jnp.any(end, axis=1)
    n_emit = jnp.where(done, jnp.argmax(end, axis=1) + 1, acc + 1)
    return out, n_emit.astype(jnp.int32), acc, done


# ---------------------------------------------------------------------------
# in-step rollback: zero rejected suffix positions back to pool-initial state
# ---------------------------------------------------------------------------
def truncate_cache(cache, start, count, c_max: int, cache_cfg=None,
                   block_tables=None):
    """Un-insert ``count`` cache positions starting at ``start`` (per slot)
    from every KV leaf — paged pools via (page, offset) from the block
    table, contiguous caches via (slot, row). Zeroing restores the exact
    initial pool state, so rewind + re-insert ≡ straight insert bit-for-bit
    (pinned by tests/test_paged_cache.py). Runs inside the jitted engine
    step; slots with count == 0 are full no-ops via scatter mode='drop'.

    ``cache`` is the engine cache pytree ({"layers": {subN: pool-or-block
    stacked [G, ...]}, optional "tail"}); ``c_max`` bounds the per-slot
    rewind width (the step's speculate_k)."""
    paged = cache_cfg is not None and cache_cfg.paged
    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    if paged:
        from repro.cache import paged_truncate
        def f(pool):
            return paged_truncate(pool, start, count, block_tables,
                                  cache_cfg, c_max)
    else:
        from repro.models.attention import cache_truncate_chunk
        def f(block):
            return jax.tree.map(
                lambda leaf: cache_truncate_chunk(leaf, start, count, c_max),
                block)
    out = {"layers": {k: jax.vmap(f)(v) for k, v in cache["layers"].items()}}
    if "tail" in cache:
        out["tail"] = {k: f(v) for k, v in cache["tail"].items()}
    return out
