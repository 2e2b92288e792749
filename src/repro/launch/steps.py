"""Jitted step builders: train_step / probe / prefill_step / serve_step with
GSPMD shardings derived from the config's logical-axis rules."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core import lp as lp_mod
from repro.models import transformer
from repro.models.blocks import block_kind
from repro.models.layers import rope_freqs
from repro.optim import optimizers
from repro.parallel import params as pshard
from repro.parallel.sharding import axis_rules


def make_train_fn(rcfg: RunConfig, mesh: Optional[Mesh]):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). Gradient accumulation over microbatches bounds the live MGRIT
    state memory (EXPERIMENTS.md §Dry-run)."""
    mode = "lp" if rcfg.mgrit.enabled else "serial"
    nmb = rcfg.microbatches

    def loss(p, b):
        l, diag = transformer.loss_fn(p, b, rcfg, mode=mode)
        return l, diag

    def train_step(params, opt_state, batch):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            if nmb > 1:
                mb = jax.tree.map(
                    lambda a: a.reshape((nmb, a.shape[0] // nmb)
                                        + a.shape[1:]), batch)

                def acc(carry, b_i):
                    g_acc, l_acc = carry
                    (l, diag), g = jax.value_and_grad(loss, has_aux=True)(
                        params, b_i)
                    g_acc = jax.tree.map(
                        lambda a, g_: a + g_.astype(jnp.float32), g_acc, g)
                    return (g_acc, l_acc + l), diag

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, lsum), diags = jax.lax.scan(acc, (g0, 0.0), mb)
                grads = jax.tree.map(lambda g: g / nmb, grads)
                lval = lsum / nmb
                diag = jax.tree.map(lambda a: a[-1], diags)
            else:
                (lval, diag), grads = jax.value_and_grad(
                    loss, has_aux=True)(params, batch)
            params2, opt_state2, om = optimizers.apply_updates(
                rcfg.optimizer, params, grads, opt_state)
        metrics = {"loss": lval, "fwd_norms": diag["fwd_norms"], **om}
        return params2, opt_state2, metrics

    return train_step


def make_probe_fn(rcfg: RunConfig, mesh: Optional[Mesh], fwd_iters: int,
                  bwd_iters: int):
    """Returns probe(params, batch) -> ``lp_diagnose``'s output: the
    paper's indicator probe, the trunk's MGRIT solves at the given
    iteration counts after the embedding and the opening buffer layers,
    traced under the same logical-axis rules as the train step."""
    cfg = rcfg.model
    kind = block_kind(cfg)
    static = lp_mod.LPStatic(
        cfg=cfg,
        mgrit=dataclasses.replace(rcfg.mgrit, fwd_iters=fwd_iters,
                                  bwd_iters=bwd_iters),
        kind=kind, causal=cfg.family != "encoder")

    # the phase scopes of lp_diagnose's solves nest inside this one
    @jax.named_scope("mgrit_probe")
    def probe(params, batch):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            z = transformer._embed_inputs(params, batch, cfg)
            rope = None if kind in ("mamba1", "mamba2") else rope_freqs(
                cfg.resolved_head_dim, cfg.rope_theta,
                jnp.arange(z.shape[1], dtype=jnp.int32))
            z = transformer._serial_buffer(params.get("open"), z, cfg,
                                           kind=kind, causal=static.causal,
                                           rope=rope)
            extra = {"rope": rope} if rope is not None else {}
            return lp_mod.lp_diagnose(
                static, params["mid"], z, extra,
                seed_ct=lambda zT: jnp.ones_like(zT)
                / jnp.asarray(zT.size, zT.dtype),
                fwd_iters=fwd_iters, bwd_iters=bwd_iters)

    return probe


def make_prefill_fn(rcfg: RunConfig, mesh: Optional[Mesh]):
    def prefill_step(params, batch):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            logits = transformer.prefill(params, batch, rcfg)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt, logits

    return prefill_step


def make_serve_fn(rcfg: RunConfig, mesh: Optional[Mesh]):
    """Dense greedy decode step builder: (params, cache, tokens (B,T)) ->
    (next (B,1), cache). T > 1 chunk-prefills the prompt into the cache in
    one call (attention kinds). This is the serial-forward oracle the
    paged backends are conformance-tested against, and the engine's dense
    comparison probe; production decode goes through
    :func:`make_paged_serve_fn` + a ``repro.serve.cache`` backend.
    """
    encdec = rcfg.model.family == "encdec"

    def serve_step(params, cache, tokens, xa=None):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            logits, cache2 = transformer.decode_step(params, cache, tokens,
                                                     rcfg, xa=xa)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt[:, None], cache2

    if not encdec:
        return lambda params, cache, tokens: serve_step(params, cache, tokens)
    return serve_step


_MASKED = -1e30          # matches the attention-mask convention


def apply_top_k(logits, k):
    """Mask all but each row's k highest logits to ``_MASKED``.

    logits: (B, V) float; k: (B,) int32, vectorized per row. ``k <= 0``
    (or ``k >= V``) disables the filter for that row. Ties at the k-th
    value are kept, so the surviving set can only be larger, never
    smaller, than k (irrelevant for real float logits).
    """
    V = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)                       # ascending
    k_eff = jnp.clip(jnp.where(k <= 0, V, k), 1, V)
    kth = jnp.take_along_axis(srt, (V - k_eff)[:, None], axis=-1)
    return jnp.where(logits < kth, _MASKED, logits)


def apply_top_p(logits, p):
    """Nucleus mask: keep each row's smallest descending-probability set
    whose cumulative mass reaches p (the argmax always survives), mask the
    rest to ``_MASKED``. logits: (B, V); p: (B,) in (0, 1], per row;
    ``p >= 1`` keeps every token with non-zero probability."""
    B, V = logits.shape
    idx = jnp.argsort(logits, axis=-1)[:, ::-1]           # descending
    srt = jnp.take_along_axis(logits, idx, axis=-1)
    probs = jax.nn.softmax(srt.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < p[:, None]     # mass before this token < p
    keep = keep.at[:, 0].set(True)
    masked_sorted = jnp.where(keep, srt, _MASKED)
    return jnp.zeros_like(logits).at[
        jnp.arange(B)[:, None], idx].set(masked_sorted)


def apply_top_k_top_p(logits, k, p):
    """Fused top-k + nucleus mask: one descending sort drives both
    filters (top-k masking preserves the survivors' order, so the
    separate argsort in :func:`apply_top_p` is redundant on the hot
    path). Semantically identical to ``apply_top_p(apply_top_k(x, k),
    p)`` for distinct logits."""
    B, V = logits.shape
    idx = jnp.argsort(logits, axis=-1)[:, ::-1]           # descending
    srt = jnp.take_along_axis(logits, idx, axis=-1)
    k_eff = jnp.clip(jnp.where(k <= 0, V, k), 1, V)
    keep = jnp.arange(V)[None, :] < k_eff[:, None]
    probs = jax.nn.softmax(
        jnp.where(keep, srt, _MASKED).astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < p[:, None]    # mass before this token < p
    keep = keep.at[:, 0].set(True)        # argmax always survives
    masked_sorted = jnp.where(keep, srt, _MASKED)
    return jnp.zeros_like(logits).at[
        jnp.arange(B)[:, None], idx].set(masked_sorted)


def sample_tokens(logits, temps, top_ks, top_ps, seeds, counters,
                  fused: bool = False):
    """Vectorized per-slot sampling: (B, V) logits -> (B,) int32 tokens.

    Slots with ``temps <= 0`` take the exact greedy argmax path (bitwise
    identical to the pre-sampling step). Others scale by temperature,
    apply top-k then top-p masks, and draw via the Gumbel-argmax trick
    with key ``fold_in(PRNGKey(seed), counter)`` — the key depends only on
    the request's own seed and how many tokens it has generated, so the
    same request reproduces the same stream in any slot and any batch
    composition.

    ``fused`` swaps the full-vocab sort in :func:`apply_top_k_top_p` for
    the sort-free threshold-search mask (``repro.kernels.ops.
    topk_topp_mask``). The key schedule and the greedy path are part of
    the sampling contract and never change; for distinct surviving
    logits the masks are identical, so the drawn tokens match too.
    """
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    def _sampled(_):
        scaled = lf / jnp.maximum(temps, 1e-6)[:, None]
        if fused:
            from repro.kernels import ops as kops
            scaled = kops.topk_topp_mask(scaled, top_ks, top_ps)
        else:
            scaled = apply_top_k_top_p(scaled, top_ks, top_ps)

        def draw(seed, counter):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
            return jax.random.gumbel(key, (lf.shape[-1],), jnp.float32)

        gumbel = jax.vmap(draw)(seeds, counters)
        sampled = jnp.argmax(scaled + gumbel, axis=-1)
        return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)

    # all-greedy batches skip the sort/top-p/gumbel work entirely (runtime
    # branch, same trace — mixed batches still decode lock-step)
    return jax.lax.cond(jnp.any(temps > 0.0), _sampled, lambda _: greedy,
                        None)


# ---------------------------------------------------------------------------
# Speculative decoding: draft sampling + acceptance (paper's coarse
# propagator as a self-speculative draft — see repro.serve.spec)
# ---------------------------------------------------------------------------


def draft_sample_tokens(logits, temps, top_ks, top_ps, seeds, counters):
    """Draft-side sampling: (B, V) logits -> (tokens (B,), probs (B, V)).

    ``probs`` is the draft's TRUE proposal distribution — the verifier's
    rejection sampling needs q(d) and the full q vector for the leftover
    distribution. Greedy slots (temps <= 0) propose the argmax with a
    one-hot q (verification then reduces to exact match). Sampled slots
    draw from the temperature-scaled top-k/top-p-masked distribution
    with the request's *draft* stream ``fold_in(fold_in(PRNGKey(seed),
    counter), 2)`` — disjoint from the canonical stream (fold 0 = accept
    u / bonus gumbel, fold 1 = leftover gumbel), so acceptance draws stay
    independent of the proposals. Distribution preservation holds for any
    proposal stream; only the acceptance rate depends on it.
    """
    lf = logits.astype(jnp.float32)
    V = lf.shape[-1]
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    g_probs = jax.nn.one_hot(greedy, V, dtype=jnp.float32)

    def _sampled(_):
        scaled = lf / jnp.maximum(temps, 1e-6)[:, None]
        masked = apply_top_k_top_p(scaled, top_ks, top_ps)
        probs = jax.nn.softmax(masked, axis=-1)

        def draw(seed, counter):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), counter), 2)
            return jax.random.gumbel(key, (V,), jnp.float32)

        gum = jax.vmap(draw)(seeds, counters)
        samp = jnp.argmax(masked + gum, axis=-1).astype(jnp.int32)
        tok = jnp.where(temps <= 0.0, greedy, samp)
        pr = jnp.where((temps <= 0.0)[:, None], g_probs, probs)
        return tok, pr

    return jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                        lambda _: (greedy, g_probs), None)


def speculative_accept(logits, tokens, draft_probs, temps, top_ks, top_ps,
                       seeds, counters, n_new):
    """Accept a drafted prefix against the fine model's own targets.

    logits: (B, S, V) fine logits over the verify window; tokens: (B, S)
    = [pending, d_1..d_k]; draft_probs: (B, k, V) proposal distributions;
    n_new: (B,) = per-slot drafted count + 1 (0 = idle slot). Position i
    of the window is the request's emission index ``counters[b] + i``, so
    every draw is keyed exactly like plain decode.

    Greedy slots: accepted = longest prefix where d_{i+1} equals the fine
    argmax — emitted tokens are bitwise what plain decode would produce.
    Sampled slots: standard speculative rejection sampling — accept d
    with prob min(1, p(d)/q(d)); on first rejection draw from the
    normalized leftover max(p - q, 0); when every draft survives, draw
    the bonus token from p at the next position with the SAME key plain
    decode would use. Either way the emitted distribution is exactly the
    target p (Leviathan et al. 2023).

    Returns (accepted (B,) in [0, n_new-1], next_token (B,)).
    """
    B, S, V = logits.shape
    k = S - 1
    lf = logits.astype(jnp.float32)
    drafts = tokens[:, 1:]
    n_draft = jnp.maximum(n_new - 1, 0)
    pos_ok = jnp.arange(k)[None, :] < n_draft[:, None]
    greedy_t = jnp.argmax(lf, axis=-1).astype(jnp.int32)          # (B, S)
    g_match = (drafts == greedy_t[:, :k]) & pos_ok
    g_acc = jnp.sum(jnp.cumprod(g_match.astype(jnp.int32), axis=1), axis=1)
    g_next = jnp.take_along_axis(greedy_t, g_acc[:, None], axis=1)[:, 0]

    def _sampled(_):
        scaled = lf / jnp.maximum(temps, 1e-6)[:, None, None]
        masked = apply_top_k_top_p(
            scaled.reshape(B * S, V),
            jnp.repeat(top_ks, S), jnp.repeat(top_ps, S)).reshape(B, S, V)
        p = jax.nn.softmax(masked, axis=-1)
        q = draft_probs.astype(jnp.float32)                        # (B, k, V)

        def slot_keys(seed, counter):
            base = jax.random.PRNGKey(seed)
            return jax.vmap(
                lambda i: jax.random.fold_in(base, counter + i))(
                jnp.arange(S))
        keys = jax.vmap(slot_keys)(seeds, counters)                # (B, S, 2)
        u = jax.vmap(jax.vmap(
            lambda kk: jax.random.uniform(kk, ())))(keys[:, :k])
        p_d = jnp.take_along_axis(p[:, :k], drafts[..., None], -1)[..., 0]
        q_d = jnp.take_along_axis(q, drafts[..., None], -1)[..., 0]
        ok = (u < p_d / jnp.maximum(q_d, 1e-30)) & pos_ok
        s_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        j = s_acc                          # first rejected index, or n_draft
        p_j = jnp.take_along_axis(p, j[:, None, None], axis=1)[:, 0]
        q_pad = jnp.concatenate([q, jnp.zeros((B, 1, V), jnp.float32)], 1)
        q_j = jnp.take_along_axis(q_pad, j[:, None, None], axis=1)[:, 0]
        rejected = j < n_draft
        res = jnp.clip(p_j - q_j, 0.0, None)
        rs = jnp.sum(res, axis=-1, keepdims=True)
        res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30), p_j)
        dist = jnp.where(rejected[:, None], res, p_j)
        key_j = jnp.take_along_axis(
            keys, jnp.broadcast_to(j[:, None, None], (B, 1, 2)),
            axis=1)[:, 0]
        left_keys = jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(key_j)
        gkey = jnp.where(rejected[:, None], left_keys, key_j)
        gum = jax.vmap(
            lambda kk: jax.random.gumbel(kk, (V,), jnp.float32))(gkey)
        s_next = jnp.argmax(jnp.log(jnp.maximum(dist, 1e-30)) + gum,
                            axis=-1).astype(jnp.int32)
        acc = jnp.where(temps > 0.0, s_acc, g_acc).astype(jnp.int32)
        nxt = jnp.where(temps > 0.0, s_next, g_next)
        return acc, nxt

    return jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                        lambda _: (g_acc.astype(jnp.int32), g_next), None)


def make_paged_verify_fn(rcfg: RunConfig, mesh: Optional[Mesh], verify_fn,
                         commit_fn=None):
    """Speculative-verification step builder: ONE jitted occupancy-masked
    call runs the FULL model over each slot's pending token + k drafted
    tokens, samples the per-position targets, computes the accepted
    prefix (:func:`speculative_accept`), and commits decode state for
    exactly the accepted prefix.

    ``verify_fn`` is the family's paged verify forward
    (``transformer.{paged,ssm_paged,hybrid_paged}_verify_step``);
    ``commit_fn`` is its deferred snapshot commit, or None for backends
    whose rollback is host-side length truncation (attention KV). The
    returned callable maps (params, state, tokens (B, k+1), lengths,
    n_new, page_table, sampling params, counters, draft_probs (B, k, V))
    -> (accepted (B,), next_token (B,), new_state).
    """
    def paged_verify_step(params, state, tokens, lengths, n_new, page_table,
                          temps, top_ks, top_ps, seeds, counters,
                          draft_probs):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            logits, state2, art = verify_fn(params, state, tokens, lengths,
                                            n_new, page_table, rcfg)
            acc, nxt = speculative_accept(logits, tokens, draft_probs,
                                          temps, top_ks, top_ps, seeds,
                                          counters, n_new)
            if commit_fn is not None:
                n_write = jnp.where(n_new > 0,
                                    jnp.minimum(acc + 1, n_new), 0)
                state2 = commit_fn(state2, art, page_table, lengths,
                                   n_write)
        return acc, nxt, state2

    return paged_verify_step


def make_draft_wave_fn(rcfg: RunConfig, mesh: Optional[Mesh], decode_fn,
                       *, k: int, page_size: int, snapshot_state: bool):
    """One fused jitted call for a whole draft wave of the coarse
    propagator: (1) the catch-up ingest — canonical tokens the draft has
    not yet cached plus the pending token, S = k+1 occupancy-masked —
    which commits TRUE state and proposes d_1; (2) k-1 in-call
    autoregressive speculative steps (a lax.scan feeding each sampled
    token back) proposing d_2..d_k. Slots stop advancing at their own
    ``n_draft``, so near-finished requests never write past capacity.

    On snapshot backends the partial state page holding the
    post-ingest committed state is saved before speculation and restored
    before returning — speculative writes to it are undone in-call, so
    the next wave's ingest resumes from true canonical state (KV drafts
    skip this: stale entries beyond the committed length are masked and
    later overwritten). Returns (drafted (B, k), draft_probs (B, k, V),
    new_state).
    """
    def draft_wave(params, state, tokens, lengths, n_in, page_table,
                   temps, top_ks, top_ps, seeds, counters, n_draft):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            logits, state = decode_fn(params, state, tokens, lengths, n_in,
                                      page_table, rcfg)
            tok, probs = draft_sample_tokens(logits, temps, top_ks, top_ps,
                                             seeds, counters)
            committed = lengths + n_in
            if snapshot_state:
                P = page_table.shape[1]
                slot = jnp.clip((committed - 1) // page_size, 0, P - 1)
                part = jnp.take_along_axis(page_table, slot[:, None],
                                           axis=1)[:, 0]
                saved = jax.tree.map(lambda a: a[:, part], state)

            def body(carry, i):
                st, ln, tk = carry
                live = ((n_in > 0) & (n_draft >= i + 2)).astype(jnp.int32)
                lg, st = decode_fn(params, st, tk[:, None], ln, live,
                                   page_table, rcfg)
                t2, p2 = draft_sample_tokens(lg, temps, top_ks, top_ps,
                                             seeds, counters + i + 1)
                return (st, ln + live, t2), (t2, p2)

            if k > 1:
                (state, _, _), (ts, ps_) = jax.lax.scan(
                    body, (state, committed, tok), jnp.arange(k - 1))
                d = jnp.concatenate([tok[:, None], ts.T], axis=1)
                q = jnp.concatenate([probs[:, None],
                                     jnp.moveaxis(ps_, 0, 1)], axis=1)
            else:
                d, q = tok[:, None], probs[:, None]
            if snapshot_state:
                state = jax.tree.map(
                    lambda a, s: a.at[:, part].set(s), state, saved)
        return d, q, state

    return draft_wave


def make_paged_serve_fn(rcfg: RunConfig, mesh: Optional[Mesh],
                        decode_fn=None, fused: bool = False):
    """Paged-state step: one jitted function serves both chunked prefill
    (S = prompt bucket) and steady-state decode (S = 1); slot occupancy is
    the ``n_new`` mask, so admissions/evictions never retrace.

    ``decode_fn`` is the family's paged forward — any of
    ``transformer.{paged,ssm_paged,hybrid_paged}_decode_step`` (possibly
    with ``page_size`` pre-bound), called as ``decode_fn(params, state,
    tokens, lengths, n_new, page_table, rcfg)``. Defaults to the attention
    KV step. The ``repro.serve.cache`` backends pick the right one, so
    every family decodes through this single wrapper.

    Sampling is vectorized per slot inside the same trace: ``temps`` /
    ``top_ks`` / ``top_ps`` are (B,) request parameters (temperature 0 =
    greedy), ``seeds``/``counters`` derive each slot's PRNG key, so mixed
    greedy/sampled batches decode lock-step with no retrace. ``fused``
    selects the sort-free sampling epilogue (see :func:`sample_tokens`).
    """
    decode_fn = decode_fn or transformer.paged_decode_step

    def paged_serve_step(params, state, tokens, lengths, n_new, page_table,
                         temps, top_ks, top_ps, seeds, counters):
        ctx = axis_rules(mesh, rcfg.sharding) if mesh is not None else \
            _nullctx()
        with ctx:
            logits, state2 = decode_fn(params, state, tokens, lengths,
                                       n_new, page_table, rcfg)
            nxt = sample_tokens(logits, temps, top_ks, top_ps, seeds,
                                counters, fused=fused)
        return nxt[:, None], state2

    return paged_serve_step


class _nullctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def shardings_for_train(rcfg: RunConfig, mesh: Mesh, params_sds,
                        opt_sds, batch_sds):
    ps = pshard.param_specs(params_sds, rcfg, mesh)
    os_ = {"step": NamedSharding(mesh, P())}
    for k in ("m", "v", "master"):
        if k in opt_sds:
            os_[k] = ps
    bs = pshard.batch_specs(batch_sds, rcfg, mesh)
    return ps, os_, bs


def shardings_for_decode(rcfg: RunConfig, mesh: Mesh, params_sds, cache_sds):
    ps = pshard.param_specs(params_sds, rcfg, mesh)
    cs = pshard.cache_specs(cache_sds, rcfg, mesh)
    ts = NamedSharding(mesh, P(None, None))
    return ps, cs, ts
