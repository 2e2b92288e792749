"""Serving cells: open-loop arrivals into the program's ``ServeEngine``.

Set-up makes the weights from the seed, builds the engine, and warms every
serve-step shape the mix can cause (``generator.step_shapes``) plus the
copy-on-write page copy, on scratch page 0. The window then submits each
request when it is due and drives ``Scheduler.step`` between arrivals;
after the window closes it keeps stepping, without new arrivals, until
every request has finished or the drain limit passes. Times are taken
from when each request was due, so a stall's delay on later arrivals
counts. Every token's arrival is stamped when the step that made it
returns, so the inter-token gaps of all requests make up the time per
output token; the pages in use are sampled beside the queue's length.
``--trace 1`` profiles ``trace_seconds`` in the middle of the
window. Once the engine is freed the plain reference runs every sampled
greedy request's prompt and served tokens and reads how far below its
best logit each served token lies.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, generator, weights


def build_rcfg(program: dict):
    from repro.configs import registry
    rcfg = registry.get_config(program["arch"], program.get("shape",
                                                            "decode_32k"))
    return rcfg.replace(
        model=dataclasses.replace(rcfg.model, **program["model"]),
        mgrit=dataclasses.replace(rcfg.mgrit, **program["mgrit"]))


class CallLog:
    """Wraps the backend's prefill/step for the profiled segment: records
    each call's per-slot (cached length, new tokens)."""

    def __init__(self, backend):
        self.backend, self.calls = backend, []
        self.orig = backend.prefill, backend.step
        backend.prefill = self._wrap(self.orig[0])
        backend.step = self._wrap(self.orig[1])

    def _wrap(self, fn):
        def call(state, slots, tokens):
            self.calls.append((np.array(slots.lengths), np.array(slots.n_new)))
            return fn(state, slots, tokens)
        return call

    def close(self):
        self.backend.prefill, self.backend.step = self.orig


class TimeWeighted:
    """Time-weighted mean and peak of a step function sampled inside
    [t0, end]."""

    def __init__(self, t0, end):
        self.t0, self.end = t0, end
        self.t, self.v, self.area, self.peak = t0, 0, 0.0, 0

    def sample(self, now, value):
        if now > self.end:
            now, value = self.end, self.v
        if now > self.t:
            self.area += self.v * (now - self.t)
            self.t = now
        self.v = value
        self.peak = max(self.peak, value)

    def mean(self):
        self.sample(self.end, self.v)
        return self.area / (self.end - self.t0)


class TokenGaps:
    """Every inter-token gap of the requests due in the window: after each
    scheduler step, a request whose output grew by k tokens since the last
    look adds k gaps of (now - last) / k; the first token counts from the
    scheduler's own stamp."""

    def __init__(self):
        self.live, self.gaps = {}, []      # id -> [handle, tokens seen, time]

    def add(self, handle):
        self.live[id(handle)] = [handle, 0, 0.0]

    def look(self, now):
        for key, (h, seen, t) in list(self.live.items()):
            n = len(h.out)
            if n > seen:
                if seen == 0:
                    seen, t = 1, h.t_first
                if n > seen:
                    self.gaps += [(now - t) / (n - seen)] * (n - seen)
                self.live[key] = [h, n, now]
            if h.done:
                del self.live[key]


class Segment:
    """The profiled part of the window (``--trace 1``): from ``at`` for
    ``seconds``, every ``Scheduler.step`` runs inside a ``TraceAnnotation``
    and on the host clock, and every serve-step call is logged."""

    def __init__(self, run, backend, at, seconds):
        self.run, self.backend = run, backend
        self.at, self.seconds = at, seconds
        self.open = self.done = False
        self.calls, self.step_s = [], 0.0

    def step(self, sched, now):
        if not (self.open or self.done) and now >= self.at:
            self.log = CallLog(self.backend)
            self.prof = common.Profile()
            self.span = jax.profiler.TraceAnnotation("bench.segment")
            self.span.__enter__()
            self.until, self.open = now + self.seconds, True
        if not self.open:
            return sched.step()
        with jax.profiler.TraceAnnotation("scheduler.step"):
            t = time.perf_counter()
            busy = sched.step()
            self.step_s += time.perf_counter() - t
        if time.perf_counter() >= self.until:
            self.close()
        return busy

    def close(self):
        if self.open:
            self.span.__exit__(None, None, None)
            self.run.trace_data = self.prof.stop(self.run.devices,
                                                 "bench.segment")
            self.log.close()
            self.calls, self.open, self.done = self.log.calls, False, True


def warm(engine, shapes, page_size):
    """One call per (tokens per slot, table width) on scratch page 0 with
    every slot idle, and the copy-on-write page copy."""
    from repro.serve.cache import SlotBatch, copy_state_page
    sched, be = engine.scheduler, engine.backend
    B = sched.max_batch
    table = np.zeros_like(sched.page_table)
    for s, w in shapes:
        lengths = np.zeros((B,), np.int32)
        lengths[0] = w * page_size
        slots = SlotBatch.greedy(B, table, lengths=lengths,
                                 n_new=np.zeros((B,), np.int32))
        toks = np.zeros((B, s), np.int32)
        fn = be.step if s == 1 else be.prefill
        sched.state, nxt = fn(sched.state, slots, toks)
        np.asarray(nxt)
    sched.state = copy_state_page(sched.state, 0, 0)
    jax.block_until_ready(sched.state)


def run(run):
    from repro.models import transformer
    from repro.serve.engine import ServeEngine

    cfg, mix = run.config, run.traffic
    program, dep = cfg["program"], cfg["deployment_settings"]
    rcfg = build_rcfg(program)
    ref = common.reference(cfg["reference"])
    dims = ref.dims_from(program["model"], program["mgrit"])
    if run.trace:
        os.environ["REPRO_PROFILE"] = "1"     # the backend's dispatch spans
    counter = common.CompileCounter()

    shapes = ref.param_shapes(dims)
    want = jax.eval_shape(lambda k: transformer.init_model(k, rcfg),
                          jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(shapes, is_leaf=ref.
                                                      is_shape_leaf) or [
            a.shape for a in jax.tree.leaves(want)] != [
            s[0] for s in jax.tree.leaves(shapes, is_leaf=ref.is_shape_leaf)]:
        raise RuntimeError("the program's parameter tree no longer matches "
                           "the reference's layout")
    pdtype = jnp.dtype(program["model"]["param_dtype"])
    params = weights.make(run.seed, shapes, pdtype, ref.is_shape_leaf)
    page = dep["page_size"]
    engine = ServeEngine(rcfg, params, max_len=dep["max_len"],
                         max_batch=dep["max_batch"], page_size=page)
    del params
    sched = engine.scheduler
    vocab = program["model"]["vocab_size"]
    reqs = generator.open_loop(mix, run.seconds, run.seed, vocab)
    warm(engine, generator.step_shapes(mix, dep["max_len"], page), page)
    n_pages = sched.alloc.n_pages
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(sched.state))
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.backend.params))
    run.setup_done()

    # ---- measured window ----
    counter.start()
    handles = [None] * len(reqs)
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    end = t0 + run.seconds
    depth, pages = TimeWeighted(t0, end), TimeWeighted(t0, end)
    tokens = TokenGaps()
    seg = Segment(run, engine.backend,
                  t0 + (run.seconds - mix["trace_seconds"]) / 2,
                  mix["trace_seconds"]) if run.trace else None
    late, closed = [], None
    while True:
        now = time.perf_counter()
        while i < n and t0 + reqs[i].due <= now:
            r = reqs[i]
            late.append(now - (t0 + r.due))
            handles[i] = sched.submit_request(
                r.prompt, r.max_new, None, temperature=r.temperature,
                top_k=r.top_k, top_p=r.top_p, seed=r.seed)
            tokens.add(handles[i])
            i += 1
        depth.sample(now, len(sched.queue))
        pages.sample(now, n_pages - 1 - sched.alloc.n_free)
        if closed is None and now >= end:
            closed = sum(len(h.out) for h in handles if h is not None)
            at_close = (len(sched.queue),
                        sum(not h.done for h in handles if h is not None))
        if closed is not None and (all(h.done for h in handles)
                                   or now > end + mix["drain_limit_s"]):
            break
        busy = seg.step(sched, now) if seg else sched.step()
        now = time.perf_counter()
        tokens.look(now)
        depth.sample(now, len(sched.queue))
        pages.sample(now, n_pages - 1 - sched.alloc.n_free)
        if not busy:
            nxt = t0 + reqs[i].due if i < n else end
            time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
    drain_s = time.perf_counter() - end
    compiles = counter.stop()
    if seg:
        seg.close()

    failed = [h for h in handles if not h.done or h.error is not None]
    good = [h for h in handles if h.done and h.error is None]
    ttft = [(h.t_first - (t0 + r.due)) * 1e3
            for h, r in zip(handles, reqs) if h.done and h.error is None]
    tpot = [g * 1e3 for g in tokens.gaps]
    run.attempted, run.failed = n, len(failed)
    run.data.update(
        kind="serve", ttft_p50_ms=common.percentile(ttft, 50),
        ttft_p90_ms=common.percentile(ttft, 90),
        tpot_p90_ms=common.percentile(tpot, 90),
        serve_tokens_per_s=closed / run.seconds,
        queue_depth_mean=depth.mean(), queue_at_close=at_close[0],
        pages_in_use_mean=pages.mean(), pages_in_use_peak=pages.peak,
        page_bytes=pool_bytes / n_pages, weight_bytes=weight_bytes,
        gaps=len(tpot), gaps_over_1s=sum(g > 1e3 for g in tpot),
        unfinished_at_close=at_close[1], drain_s=drain_s,
        late_p90_ms=common.percentile([x * 1e3 for x in late], 90),
        model=program["model"])
    if seg:
        run.data.update(step_s=seg.step_s, calls=seg.calls)
    run.read_memory()

    # the sample: the longest greedy request, then others drawn from the
    # seed, until enough served tokens are covered
    rng = np.random.default_rng(run.seed)
    done_greedy = [(h, r) for h, r in zip(handles, reqs)
                   if r.greedy and h.done and h.error is None]
    done_greedy.sort(key=lambda hr: -(len(hr[0].prompt) + len(hr[0].out)))
    sample = done_greedy[:1]
    rest = [done_greedy[j] for j in rng.permutation(len(done_greedy))[:]
            if j != 0]
    while rest and sum(len(h.out) for h, _ in sample) < mix["check_tokens"]:
        sample.append(rest.pop(0))
    seqs = [(np.concatenate([h.prompt, np.asarray(h.out[:-1], np.int32)]),
             len(h.prompt), np.asarray(h.out, np.int32)) for h, _ in sample]
    del engine, sched, handles, seg, tokens
    gc.collect()

    # ---- the reference ----
    t_ref = time.perf_counter()
    with jax.default_device(run.devices[0]):
        params = weights.make(run.seed, shapes, pdtype, ref.is_shape_leaf)
        gap, c_gap, n_tok = gaps(ref, dims, params, seqs, dep["max_len"],
                                 control=run.control)
    run.data["reference_s"] = time.perf_counter() - t_ref
    run.compare({"logit_gap": gap}, {"logit_gap": c_gap})
    run.check("compiles_in_window", compiles, 0)
    run.note(f"pages in use: mean {pages.mean():.1f}, peak {pages.peak} "
             f"of {n_pages - 1} ({pool_bytes / n_pages:.0f} bytes each); "
             f"weights {weight_bytes} bytes")
    run.note("ttft ms by request: " + " ".join(f"{x:.0f}" for x in ttft))
    run.note(f"{len(tpot)} inter-token gaps, "
             f"{run.data['gaps_over_1s']} over 1 s")
    run.note(f"{len(seqs)} requests, {n_tok} served tokens compared; "
             f"{len(good)}/{n} finished; generator late p90 "
             f"{run.data['late_p90_ms']} ms")


def gaps(ref, dims, params, seqs, length, control=False):
    """Widest gap, over the served tokens, between the reference's best
    logit and its logit of the served token; with ``control`` also the
    same gap of the token the fp8 reference ranks first."""
    f32 = ref.make_logits_fn(dims, "f32")
    f8 = ref.make_logits_fn(dims, "fp8") if control else None

    @jax.jit
    def read(logits, pos, tok):
        rows = logits[pos]
        return jnp.max(rows, -1) - jnp.take_along_axis(
            rows, tok[:, None], -1)[:, 0]

    worst = c_worst = 0.0
    n_tok = 0
    for seq, n_prompt, out in seqs:
        toks = np.zeros((length,), np.int32)
        toks[:len(seq)] = seq
        pos = np.arange(n_prompt - 1, n_prompt - 1 + len(out))
        lg = f32(params, toks)
        worst = max(worst, float(jnp.max(read(lg, pos, out))))
        n_tok += len(out)
        if f8 is not None:
            pick = jnp.argmax(f8(params, toks)[pos], -1)
            c_worst = max(c_worst, float(jnp.max(read(lg, pos, pick))))
    return worst, c_worst, n_tok
