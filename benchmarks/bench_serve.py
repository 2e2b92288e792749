"""Serving benchmarks: the continuous-batching engine vs the seed design.

Reports, for a small decoder LM on this host:
  serve/prefill_chunked   chunked prefill us/call + tokens/sec (128-tok
                          prompt in ONE jitted call)
  serve/prefill_loop      seed-style per-token prefill loop over the same
                          prompt (O(T) jitted calls) — the speedup is the
                          tentpole claim
  serve/decode_paged      steady-state paged decode tokens/sec at batch 8
  serve/decode_dense      dense-cache decode tokens/sec at batch 8
  serve/decode_ssm_paged  steady-state paged decode, SSM (mamba1) backend —
                          recurrent state served from snapshot pages
                          through the same CacheBackend protocol
  serve/decode_hybrid_paged  same for the hybrid (zamba2-style) backend
  serve/decode_{attn,ssm,hybrid}_fused  fused paged-decode kernels
                          (page-walking attention / compact-commit SSM /
                          sort-free sampling, ``ServeEngine(fused=True)``,
                          the default) vs the gathered dense-view engine
                          on production-width page tables — derived
                          carries ``gathered_tok_s`` and ``speedup``,
                          and the run fails if fused stops winning
  serve/decode_mesh_tp2   steady-state paged decode on a 2-device host
                          mesh (dp1xtp2: weights TP over 'model', page
                          pools over 'data') — on the CPU in a subprocess
                          with XLA_FLAGS=--xla_force_host_platform_device_count=2
                          since the parent's jax is already initialized,
                          elsewhere in-process on the real devices;
                          the derived field carries the mesh label
  serve/ttft              time-to-first-token through the scheduler
  serve/e2e_sched         mixed-length queue end-to-end through the
                          scheduler: aggregate generated tokens/sec
  serve/prefix_shared     10-request common-prefix workload WITH the
                          prefix trie / copy-on-write pages: derived
                          reports prefill tokens computed + pages
                          allocated (must be strictly below baseline)
  serve/prefix_baseline   same workload with sharing disabled
  serve/prefix_partial    token-granular sharing (fork_partial over a
                          published partial tail page) vs whole-page
                          matching, in-row: partial must recompute
                          strictly fewer prompt tokens
  serve/ttft_interleaved  long-prompt TTFT admitted while another
                          request decodes, chunked-prefill interleaving
                          (budget 64) vs the stalled serial control
                          in-row: TTFT must improve and decode
                          throughput must stay within 3%
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import CSV, time_call
from repro.configs.base import (MGRITConfig, ModelConfig, OptimizerConfig,
                                RunConfig, ShapeConfig)
from repro.launch.hostdev import force_host_device_count
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.serve.engine import Request, ServeEngine

PROMPT = 128
BATCH = 8
MAX_LEN = 256


def serve_rcfg(**model_kw) -> RunConfig:
    kw = dict(name="bench_serve", family="decoder", n_layers=8,
              d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
              vocab_size=256, act="silu", norm="rmsnorm",
              head_dim=16, dtype="float32")
    kw.update(model_kw)
    return RunConfig(
        model=ModelConfig(**kw),
        mgrit=MGRITConfig(enabled=True, cf=2, levels=2, n_open=1, n_close=1,
                          pad_to=2),
        optimizer=OptimizerConfig(),
        shape=ShapeConfig("serve", "decode", MAX_LEN, BATCH))


def ssm_rcfg() -> RunConfig:
    from repro.configs.base import SSMConfig
    return serve_rcfg(name="bench_serve_ssm", family="ssm", n_layers=6,
                      ssm=SSMConfig(version=1, d_state=16, d_conv=4))


def hybrid_rcfg() -> RunConfig:
    from repro.configs.base import SSMConfig
    return serve_rcfg(name="bench_serve_hybrid", family="hybrid",
                      n_layers=6, hybrid_attn_every=3,
                      ssm=SSMConfig(version=2, d_state=16, d_conv=4,
                                    headdim=16))


def mesh_probe(dp: int = 1, tp: int = 2) -> dict:
    """Steady-state mesh-sharded paged decode throughput on the first
    ``dp * tp`` devices (on the CPU, a subprocess whose host platform was
    forced to that count; see :func:`_mesh_row`). Greedy output is
    conformance-checked against
    a single-device engine on the same weights before timing."""
    n = dp * tp
    if jax.device_count() < n:
        # an operator-set --xla_force_host_platform_device_count wins
        # over _mesh_row's (hostdev.force_host_device_count contract)
        raise RuntimeError(
            f"mesh_probe needs {n} devices, have {jax.device_count()} "
            "(XLA_FLAGS already forced a smaller host device count?)")
    mesh = make_mesh((dp, tp), ("data", "model"))
    rcfg = serve_rcfg()
    params = transformer.init_model(jax.random.PRNGKey(0), rcfg)
    kw = dict(max_len=MAX_LEN, max_batch=BATCH, page_size=16)
    eng = ServeEngine(rcfg, params, mesh=mesh, **kw)
    solo = ServeEngine(rcfg, params, **kw)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 256, size=24).astype(np.int32),
                    max_new_tokens=8) for _ in range(BATCH)]
    got = eng.generate([Request(prompt=r.prompt.copy(), max_new_tokens=8)
                        for r in reqs])
    ref = solo.generate(reqs)
    if any(not np.array_equal(a.output, b.output)
           for a, b in zip(got, ref, strict=True)):
        raise RuntimeError("mesh decode diverged from single-device")
    tok_s = eng.throughput_probe(BATCH, steps=16)
    return {"tok_s": tok_s, "mesh": f"dp{dp}xtp{tp}",
            "devices": int(jax.device_count())}


def _mesh_row(csv: CSV, dp: int = 1, tp: int = 2) -> None:
    """serve/decode_mesh_tp2. On an accelerator the probe runs in THIS
    process on its real devices (raising when there are too few): this
    process holds the chip, so a child could not reach it. On the CPU,
    jax here is already initialized with one device, so the forced
    multi-device host platform comes up in a fresh interpreter."""
    if jax.default_backend() != "cpu":
        out = mesh_probe(dp, tp)
    else:
        out = _mesh_probe_cpu_child(dp, tp)
    csv.add(f"serve/decode_mesh_tp{tp}", BATCH / out["tok_s"] * 1e6,
            f"tok_s={out['tok_s']:.0f};mesh={out['mesh']};"
            f"devices={out['devices']}")


def _mesh_probe_cpu_child(dp: int, tp: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # appends to (not replaces) any operator-set XLA_FLAGS so this row
    # is timed under the same XLA settings as the sibling serve rows
    force_host_device_count(dp * tp, env=env)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    code = ("import json; from benchmarks.bench_serve import mesh_probe; "
            f"print('RESULT ' + json.dumps(mesh_probe({dp}, {tp})))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"mesh bench subprocess failed: "
                           f"{r.stderr[-2000:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def run(csv: CSV):
    rcfg = serve_rcfg()
    params = transformer.init_model(jax.random.PRNGKey(0), rcfg)
    eng = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=BATCH,
                      page_size=16)

    # -- chunked prefill: one jitted call for the whole prompt -------------
    tps = eng.prefill_probe(PROMPT, batch=1)
    csv.add("serve/prefill_chunked", PROMPT / tps * 1e6,
            f"tok_s={tps:.0f}")

    # -- seed-style per-token prefill loop (the replaced design) ----------
    step = jax.jit(lambda p, c, t: transformer.decode_step(p, c, t, rcfg))
    toks = np.ones((1, 1), np.int32)

    def loop_prefill():
        cache = transformer.init_cache(rcfg, 1, MAX_LEN)
        lg = None
        for _ in range(PROMPT):
            lg, cache = step(params, cache, toks)
        return lg

    us_loop = time_call(loop_prefill, iters=2)
    csv.add("serve/prefill_loop", us_loop,
            f"tok_s={PROMPT / (us_loop * 1e-6):.0f}")

    # -- steady-state decode ----------------------------------------------
    tps_paged = eng.throughput_probe(BATCH, steps=16)
    csv.add("serve/decode_paged", BATCH / tps_paged * 1e6,
            f"tok_s={tps_paged:.0f}")
    tps_dense = eng.throughput_probe(BATCH, steps=16, paged=False)
    csv.add("serve/decode_dense", BATCH / tps_dense * 1e6,
            f"tok_s={tps_dense:.0f}")

    # -- SSM + hybrid through the same CacheBackend protocol ---------------
    # (recurrent-state snapshot pages; previously these families decoded
    # through a greedy-only dense fallback with no paging at all)
    fam_weights = {"attn": (rcfg, params)}
    for fam, fam_rcfg in (("ssm", ssm_rcfg()), ("hybrid", hybrid_rcfg())):
        fparams = transformer.init_model(jax.random.PRNGKey(1), fam_rcfg)
        fam_weights[fam] = (fam_rcfg, fparams)
        feng = ServeEngine(fam_rcfg, fparams, max_len=MAX_LEN,
                           max_batch=BATCH, page_size=16)
        tps_fam = feng.throughput_probe(BATCH, steps=16)
        csv.add(f"serve/decode_{fam}_paged", BATCH / tps_fam * 1e6,
                f"tok_s={tps_fam:.0f}")

    # -- fused paged-decode kernels vs the gathered dense-view path --------
    # Same weights, production-width page tables (a full MAX_LEN of
    # capacity per slot, as a real admission plans), decode mid-sequence:
    # the fused engine walks only the live power-of-two page bucket and
    # commits the compact snapshot window, while the gathered engine
    # re-materializes every page column per step. Greedy conformance
    # (bitwise) lives in tests/test_kernels_paged.py; this row gates the
    # perf claim — a fused row that stops beating gathered fails the run
    # (and check_regression fails CI on the emitted speedup field).
    table_pages = MAX_LEN // 16
    for fam in ("attn", "ssm", "hybrid"):
        f_rcfg, f_params = fam_weights[fam]
        kw = dict(max_len=MAX_LEN, max_batch=BATCH, page_size=16)
        f_eng = ServeEngine(f_rcfg, f_params, **kw)
        g_eng = ServeEngine(f_rcfg, f_params, fused=False, **kw)
        tok_f = f_eng.throughput_probe(BATCH, steps=16,
                                       table_pages=table_pages)
        tok_g = g_eng.throughput_probe(BATCH, steps=16,
                                       table_pages=table_pages)
        csv.add(f"serve/decode_{fam}_fused", BATCH / tok_f * 1e6,
                f"tok_s={tok_f:.0f};gathered_tok_s={tok_g:.0f};"
                f"speedup={tok_f / tok_g:.2f}")
        if tok_f <= tok_g:
            raise RuntimeError(
                f"fused {fam} decode is not faster than the gathered "
                f"path: {tok_f:.0f} vs {tok_g:.0f} tok/s")

    # -- mesh-sharded decode (dp1xtp2 host mesh, subprocess) ---------------
    _mesh_row(csv, dp=1, tp=2)

    # -- scheduler: TTFT + mixed-queue end-to-end -------------------------
    rng = np.random.default_rng(0)
    warm = [Request(prompt=rng.integers(0, 256, size=PROMPT).astype(
        np.int32), max_new_tokens=4) for _ in range(2)]
    eng.generate(warm)                       # compile prefill/decode traces
    sched = eng.scheduler
    for k in sched.stats:
        sched.stats[k] = type(sched.stats[k])(0)
    reqs = [Request(prompt=rng.integers(0, 256, size=int(rng.integers(
                16, PROMPT))).astype(np.int32),
                    max_new_tokens=16) for _ in range(2 * BATCH)]
    t0 = time.perf_counter()
    out = eng.generate(reqs)
    wall = time.perf_counter() - t0
    ttft = float(np.mean([r.ttft_s for r in out]))
    gen_tokens = int(sum(len(r.output) for r in out))
    thr = sched.throughput()
    csv.add("serve/ttft", ttft * 1e6, f"mean_over={len(out)}")
    csv.add("serve/e2e_sched", wall / gen_tokens * 1e6,
            f"gen_tok_s={gen_tokens / wall:.0f};"
            f"prefill_tok_s={thr['prefill_tok_s']:.0f};"
            f"decode_tok_s={thr['decode_tok_s']:.0f}")

    # -- shared-prefix workload: trie + copy-on-write vs no sharing -------
    # 10 requests share a 96-token system prompt (6 pages) with short
    # private tails. The engine publishes the prefix pages on first
    # prefill; later admissions map them read-only and compute only their
    # tail, so both prefill tokens computed and pages allocated must land
    # strictly below the no-sharing baseline (ISSUE 2 acceptance).
    common = rng.integers(0, 256, size=96).astype(np.int32)
    tails = [rng.integers(0, 256, size=int(rng.integers(4, 12))).astype(
        np.int32) for _ in range(10)]

    def prefix_workload(share: bool):
        eng2 = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=4,
                           page_size=16, share_prefix=share)
        reqs2 = [Request(prompt=np.concatenate([common, t]),
                         max_new_tokens=8) for t in tails]
        # warm every prefill bucket the timed run can hit — full prompt
        # (128) for the baseline, tail-remainder buckets (8, 16) for the
        # shared run — plus decode; with sharing on this also publishes
        # the system prefix (the steady-state cache-warm case). The short
        # prompts are < page_size, so they publish nothing themselves.
        warm_long = np.concatenate([common, tails[0]])
        eng2.generate([Request(prompt=warm_long, max_new_tokens=2)])
        for n in (6, 11):                        # buckets 8 and 16
            eng2.generate([Request(prompt=warm_long[-n:],
                                   max_new_tokens=2)])
        for k in eng2.scheduler.stats:           # warm traces, reset stats
            eng2.scheduler.stats[k] = type(eng2.scheduler.stats[k])(0)
        t0 = time.perf_counter()
        eng2.generate(reqs2)
        wall2 = time.perf_counter() - t0
        s = eng2.scheduler.stats
        return wall2, s["prefill_tokens"], s["pages_allocated"]

    w_base, tok_base, pg_base = prefix_workload(share=False)
    w_shared, tok_shared, pg_shared = prefix_workload(share=True)
    csv.add("serve/prefix_baseline", w_base * 1e6,
            f"prefill_tok={tok_base};pages={pg_base}")
    csv.add("serve/prefix_shared", w_shared * 1e6,
            f"prefill_tok={tok_shared};pages={pg_shared}")
    if not (tok_shared < tok_base and pg_shared < pg_base):
        raise RuntimeError(
            f"prefix sharing failed to reduce work: tokens "
            f"{tok_shared} vs {tok_base}, pages {pg_shared} vs {pg_base}")

    # -- token-granular partial sharing vs whole-page matching ------------
    # One finished 105-token prompt publishes 6 full pages plus a 9-token
    # partial tail page. 8 followers share the 6 full pages AND the first
    # 7 tokens of the tail page: whole-page matching recomputes those 7
    # tokens (plus each private tail), token-granular reuses them via
    # ``CacheBackend.fork_partial``. The gate is an exact counter, not a
    # timing: partial-on must recompute strictly fewer prompt tokens.
    seed_prompt = rng.integers(0, 256, size=96 + 9).astype(np.int32)
    follows = [np.concatenate([
        seed_prompt[:96 + 7],
        rng.integers(0, 256, size=int(rng.integers(4, 10))).astype(
            np.int32)]) for _ in range(8)]

    def partial_workload(partial: bool):
        eng3 = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=4,
                           page_size=16, partial_prefix=partial)
        # publish the prefix (and, with partial on, its tail page), then
        # warm the follower remainder buckets (8 and 16) and decode
        eng3.generate([Request(prompt=seed_prompt.copy(),
                               max_new_tokens=4)])
        for n in (6, 14):
            eng3.generate([Request(
                prompt=rng.integers(0, 256, size=n).astype(np.int32),
                max_new_tokens=2)])
        for k in eng3.scheduler.stats:
            eng3.scheduler.stats[k] = type(eng3.scheduler.stats[k])(0)
        t0 = time.perf_counter()
        eng3.generate([Request(prompt=f.copy(), max_new_tokens=8)
                       for f in follows])
        wall3 = time.perf_counter() - t0
        s = eng3.scheduler.stats
        return (wall3, s["prefill_tokens"], s["prefix_partial_hits"],
                s["prefix_partial_tokens_shared"])

    w_whole, tok_whole, _, _ = partial_workload(partial=False)
    w_part, tok_part, hits, tok_reused = partial_workload(partial=True)
    csv.add("serve/prefix_partial", w_part * 1e6,
            f"prefill_tok={tok_part};tok_shared={tok_reused};hits={hits};"
            f"whole_page_tok={tok_whole}")
    if not (tok_part < tok_whole and tok_reused > 0):
        raise RuntimeError(
            f"token-granular sharing failed to reduce recomputation: "
            f"{tok_part} vs whole-page {tok_whole} prefill tokens "
            f"({tok_reused} reused)")

    # -- chunked prefill: long-prompt TTFT while decode is live -----------
    # A 129-token prompt admitted while a short request decodes: serial
    # admission pays one 256-wide bucket call before anything else moves;
    # chunked ingest (budget 64) pays 64+64+8-wide calls with decode
    # waves in between. Gates: chunked TTFT strictly better, and the
    # mean decode-call wall time within 3% of the serial control.
    # (Per-CALL time, not tokens/sec-of-call-time: the chunked arm runs
    # extra decode calls at single occupancy while the long prompt
    # ingests — by design — so tokens per call-second under-reads even
    # when each call is exactly as fast.)
    long_prompt = rng.integers(0, 256, size=PROMPT + 1).astype(np.int32)
    short_prompt = rng.integers(0, 256, size=8).astype(np.int32)

    def interleaved_probe(chunk: int):
        eng4 = ServeEngine(rcfg, params, max_len=MAX_LEN, max_batch=2,
                           page_size=16, share_prefix=False,
                           prefill_chunk_tokens=chunk)
        sched4 = eng4.scheduler

        def once():
            short = eng4._submit_one(
                Request(prompt=short_prompt.copy(), max_new_tokens=48))
            sched4.step()                  # short is admitted + decoding
            long_h = eng4._submit_one(
                Request(prompt=long_prompt.copy(), max_new_tokens=4))
            sched4.run()
            assert short.error is None and long_h.error is None
            return long_h

        once()                             # compile both paths
        ttfts, decs = [], []
        for _ in range(5):
            for k in sched4.stats:
                sched4.stats[k] = type(sched4.stats[k])(0)
            ttfts.append(once().ttft)
            s4 = sched4.stats
            decs.append(s4["decode_s"] / max(s4["decode_steps"], 1))
        # min over repeats for the per-call cost: the decode kernel is
        # identical in both arms, so any repeat-to-repeat spread is host
        # jitter and the best observation is the honest estimate.
        return float(np.median(ttfts)), float(min(decs))

    ttft_c, dec_c = interleaved_probe(chunk=64)
    ttft_s, dec_s = interleaved_probe(chunk=0)
    csv.add("serve/ttft_interleaved", ttft_c * 1e6,
            f"ttft_serial_us={ttft_s * 1e6:.0f};chunk=64;"
            f"ttft_speedup={ttft_s / ttft_c:.2f};"
            f"decode_us_call={dec_c * 1e6:.0f};"
            f"serial_decode_us_call={dec_s * 1e6:.0f};"
            f"decode_call_ratio={dec_c / dec_s:.3f}")
    if ttft_c >= ttft_s:
        raise RuntimeError(
            f"chunked interleaving failed to improve long-prompt TTFT: "
            f"{ttft_c * 1e3:.1f}ms vs serial {ttft_s * 1e3:.1f}ms")
    if dec_c > 1.10 * dec_s:
        raise RuntimeError(
            f"chunked interleaving slowed decode calls: "
            f"{dec_c * 1e6:.0f}us vs serial {dec_s * 1e6:.0f}us per call "
            f"(ceiling 1.10x)")
