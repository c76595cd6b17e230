"""Serving launcher: batched prefill + decode with a KV/state cache, plus a
batched SpMV serving mode built on the plan-once engine.

`python -m repro.launch.serve --arch <id> --reduced --tokens 32` runs a
batched generation loop on CPU; on TPU the same path serves the full config
on the production mesh.

`python -m repro.launch.serve --arch <id> --reduced --paged --decode-steps N`
serves end-to-end paged decode instead: per-layer paged KV caches
(models.paged_kv) whose page gathers resolve through the shared
`core.gather_engine` plan cache, with per-layer gather plan reports,
tokens/s, a paged-vs-dense parity gate, and a zero-steady-state-plan-builds
assertion (the static page table keeps every decode step on one cached
engine).

`python -m repro.launch.serve --spmv banded --batch 64 --requests 8` stands up
an `SpMVEngine` for one matrix and serves batches of right-hand sides through
the cached coalescer plan (`matmat`), reporting steady-state throughput — the
thousands-of-RHS regime the schedule cache exists for. Add `--mesh data,model`
to shard row slices over the mesh's data axis and RHS columns over model
(`core.dist.ShardedSpMVEngine`), with per-shard coalesce stats and per-device
throughput in the report. Add `--stream depth=D,microbatch=B` to serve through
`core.runtime.StreamingExecutor` — requests are micro-batched and pipelined so
host->device RHS staging overlaps compute on the previous micro-batch, with a
bounded in-flight queue; the report then carries the synchronous loop, the
streamed loop, the measured speedup, and the perf model's overlap
prediction."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import faults
from repro.core import matrices as _matgen
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, make_input_batch
from repro.models.transformer import Runtime


def generate(model, params, prompt, *, max_new_tokens: int, rt: Runtime,
             extras_batch=None, greedy: bool = True, key=None):
    """Prefill the prompt (one multi-token decode_step), then decode."""
    B, S = prompt.shape
    cache = model.init_cache(B, S + max_new_tokens, rt)
    if model.cfg.family == "audio":
        cache["enc_out"] = model.extras["encode"](
            params, extras_batch["enc_input"], rt
        )
    if model.cfg.family == "vlm":
        cache["image_embeds"] = extras_batch["image_embeds"]
    logits, cache = model.decode_step(params, prompt, cache, rt)
    outs = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    step_fn = jax.jit(
        lambda p, t, c: model.decode_step(p, t, c, rt)
    )
    for i in range(max_new_tokens):
        outs.append(tok)
        logits, cache = step_fn(params, tok, cache)
        if greedy:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1])[:, None].astype(
                jnp.int32
            )
    return jnp.concatenate(outs, axis=1)


def serve_paged(args) -> None:
    """End-to-end paged decode: per-layer paged KV caches, a prefill +
    `append_token`/`paged_attention` decode loop, per-layer gather plan
    reports from the shared `GatherEngine`, tokens/s, and a paged-vs-dense
    parity gate (the paged path must reproduce `_sdpa` over the same K/V).

    The static allocator keeps every layer's page table constant across
    decode steps, so all steady-state gathers resolve through ONE cached
    engine — the loop asserts zero schedule builds after the first step."""
    from repro.core.engine import schedule_cache_stats
    from repro.core.gather_engine import gather_engine_cache_stats
    from repro.models.layers import _sdpa
    from repro.models.paged_kv import (
        alloc_paged, append_token, kv_plan_report, paged_attention,
    )

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    B, L = args.batch, cfg.n_layers
    n_kv, hd, H = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_heads
    block, steps = args.page_block, args.decode_steps
    max_len = args.prompt_len + steps
    max_pages = -(-max_len // block)
    # serve's --backend names the SpMV backends; the gather engine calls the
    # pure-jnp data path "coalesced" and accepts "reference" as its alias.
    backend = args.backend
    print(
        f"paged-serve: {args.arch} ({'reduced' if args.reduced else 'full'}) "
        f"layers={L} batch={B} n_kv={n_kv} head_dim={hd} heads={H} "
        f"page_block={block} prompt={args.prompt_len} decode={steps} "
        f"backend={backend}"
    )

    # One paged cache per layer (pool sized exactly for the batch), plus a
    # dense mirror of everything appended — the parity reference.
    caches = [
        alloc_paged(
            n_pages=B * max_pages, block=block, n_kv=n_kv, hd=hd,
            batch=B, max_len=max_len, dtype=jnp.float32,
        )
        for _ in range(L)
    ]
    dense_k = np.zeros((L, B, max_len, n_kv, hd), np.float32)
    dense_v = np.zeros((L, B, max_len, n_kv, hd), np.float32)
    rng = np.random.default_rng(args.seed)

    def append_all(pos: int) -> None:
        """One token's K/V per layer into both the paged and dense caches."""
        for li in range(L):
            k = rng.standard_normal((B, n_kv, hd)).astype(np.float32)
            v = rng.standard_normal((B, n_kv, hd)).astype(np.float32)
            dense_k[li, :, pos] = k
            dense_v[li, :, pos] = v
            caches[li] = append_token(
                caches[li], jnp.asarray(k), jnp.asarray(v)
            )

    # --- prefill: stage the prompt into every layer's cache
    t0 = time.time()
    for pos in range(args.prompt_len):
        append_all(pos)
    prefill_s = time.time() - t0

    # --- decode loop: append one token then attend over the paged cache,
    # checking every layer against dense SDPA on the mirrored K/V
    max_err = 0.0
    builds_after_first = None
    t0 = time.time()
    for step in range(steps):
        pos = args.prompt_len + step
        append_all(pos)
        cur = pos + 1
        mask = jnp.ones((B, 1, 1, cur), bool)
        for li in range(L):
            q = jnp.asarray(
                rng.standard_normal((B, 1, H, hd)).astype(np.float32)
            )
            out_p = paged_attention(
                q, caches[li], n_heads=H, backend=backend
            )
            out_d = _sdpa(
                q, jnp.asarray(dense_k[li, :, :cur]),
                jnp.asarray(dense_v[li, :, :cur]), mask,
            )
            max_err = max(
                max_err,
                float(np.abs(np.asarray(out_p) - np.asarray(out_d)).max()),
            )
        if step == 0:
            builds_after_first = schedule_cache_stats()["built"]
    decode_s = time.time() - t0
    builds_warm = schedule_cache_stats()["built"] - builds_after_first

    # --- per-layer gather plan report (identical tables -> one shared plan)
    for li in range(L):
        rep = kv_plan_report(caches[li], backend=backend)
        gp = rep["gather_perf"]
        print(
            f"  layer {li}: pages={rep['n_indices']} "
            f"wide_accesses={rep['wide_accesses']} "
            f"coalesce_rate={rep['coalesce_rate']:.2f} "
            f"cached={rep['schedule_cached']} "
            f"meta_bytes={rep['metadata']['meta_bytes']} "
            f"model_speedup=x{gp['speedup']:.2f}"
        )
    toks = B * steps
    print(
        f"  prefill {args.prompt_len} tokens in {prefill_s:.3f}s; decoded "
        f"{steps} steps x {B} requests in {decode_s:.3f}s "
        f"({toks / max(decode_s, 1e-12):.1f} tok/s, {L} layers)"
    )
    stats = schedule_cache_stats()
    eng_stats = gather_engine_cache_stats()
    print(
        f"  parity vs dense cache: max_abs_err={max_err:.2e} (tol=1e-5); "
        f"plan builds: total={stats['built']}, steady-state={builds_warm}; "
        f"engine cache: {eng_stats}"
    )
    if not (max_err <= 1e-5):
        raise SystemExit(
            f"paged-serve: paged attention diverged from the dense cache "
            f"(max_abs_err={max_err:.3e} > 1e-5)"
        )
    if builds_warm != 0:
        raise SystemExit(
            f"paged-serve: plan-reuse violation — {builds_warm} schedule "
            f"build(s) after the first decode step (expected 0)"
        )
    if faults.active_plan() is not None and args.schedule_cache:
        # Chaos drill: paged decode plans in memory only, so round-trip one
        # layer's gather plan through the self-healing store to give the
        # store fault sites something to hit (store_write retries inside
        # persist; store_read corruption heals via quarantine + re-persist).
        from repro.core import schedule_store
        from repro.models.paged_kv import _kv_engine

        eng = _kv_engine(caches[0], backend=backend)
        eng.schedule  # force the plan before persisting
        path = eng.persist_schedule(args.schedule_cache)
        healed = "clean"
        try:
            schedule_store.load_schedule(
                path, expect_stream_digest=eng.digest
            )
        except schedule_store.ScheduleCacheMismatch:
            schedule_store.quarantine(path)
            eng.persist_schedule(args.schedule_cache)
            faults.note_recovered("store_read")
            healed = "quarantined + re-persisted"
            with faults.suspended():  # oracle read: verify the healed file
                try:
                    schedule_store.load_schedule(
                        path, expect_stream_digest=eng.digest
                    )
                except Exception as exc:
                    raise SystemExit(
                        f"paged-serve: gather plan unreadable after "
                        f"quarantine + re-persist: {exc!r}"
                    )
        print(f"  chaos store drill: gather plan round-trip {healed}")


_SPMV_MATRICES = {
    "banded": lambda n: _matgen.banded(n, 24, 0.8),
    "powerlaw": lambda n: _matgen.powerlaw(n, 12),
    "random": lambda n: _matgen.random_uniform(n, 16),
}


def serve_solve(args) -> None:
    """Iterative-solver serving: one plan-once engine, a device-resident
    `lax.while_loop` per solve (core.solvers). Prints the cold solve
    (including how many coalescing schedules were built — exactly one) and
    warm-solve throughput in iterations/s over --requests repeats."""
    from repro.core import solvers
    from repro.core.matrices import make_spd

    gen = _SPMV_MATRICES[args.spmv](args.spmv_rows)
    csr = gen(seed=args.seed)
    if args.solve in ("cg", "jacobi"):
        csr = make_spd(csr)  # CG/Jacobi need SPD / diag-dominant input
    kw = dict(
        backend=args.backend, window=args.window, block_rows=args.block_rows,
        cache_dir=args.schedule_cache,
    )
    solver = {
        "cg": lambda m, b: solvers.cg(m, b, tol=1e-6, **kw),
        "jacobi": lambda m, b: solvers.jacobi(m, b, tol=1e-6, **kw),
        "pagerank": lambda m, b: solvers.pagerank(m, tol=1e-7, **kw),
        "power": lambda m, b: solvers.power_iteration(m, tol=1e-5, **kw),
    }[args.solve]
    b = np.random.default_rng(args.seed + 1).standard_normal(
        csr.n_rows
    ).astype(np.float32)

    t0 = time.time()
    cold = solver(csr, b)
    cold_s = time.time() - t0
    print(
        f"solve-serve: {args.solve} on {args.spmv} {csr.n_rows}x"
        f"{csr.n_cols} nnz={csr.data.size} backend={args.backend}"
    )
    print(
        f"  cold: {cold.iterations} iters in {cold_s:.3f}s "
        f"(schedule_builds={cold.schedule_builds}, loop={cold.loop})"
    )
    t0 = time.time()
    iters = 0
    res = cold
    for _ in range(max(1, args.requests)):
        res = solver(csr, b)
        iters += res.iterations
    warm_s = time.time() - t0
    extra = (
        f" eigenvalue={res.eigenvalue:.6g}" if res.eigenvalue is not None
        else ""
    )
    print(
        f"  warm: {max(1, args.requests)} solves, "
        f"{iters / warm_s:.1f} iters/s "
        f"(schedule_builds={res.schedule_builds}, residual="
        f"{res.residual:.3e}, converged={res.converged}{extra})"
    )
    if not res.converged:
        raise SystemExit(f"solve-serve: {args.solve} did not converge")
    if cold.schedule_builds != 1 or res.schedule_builds != 0:
        raise SystemExit(
            f"solve-serve: plan-reuse violation (cold built "
            f"{cold.schedule_builds}, warm built {res.schedule_builds})"
        )


def serve_spmv(args) -> None:
    """Batched SpMV serving: one engine, many right-hand-side batches.

    With ``--mesh`` the matrix is row-sharded over the mesh's ``data`` axis
    and RHS columns over ``model`` (core.dist.ShardedSpMVEngine); the report
    then includes per-shard coalesce stats and per-device throughput."""
    from repro.core.engine import get_engine, schedule_cache_stats
    from repro.core.runtime import StreamingExecutor, parse_stream_spec

    gen = _SPMV_MATRICES[args.spmv](args.spmv_rows)
    csr = gen(np.random.default_rng(args.seed))
    # Plan knobs: CLI defaults, unless the autotuner picks them. The tuned
    # cols_per_chunk implies the pallas window, so an explicit --window is
    # dropped in favor of the derived one when tuning.
    knobs = dict(window=args.window, block_rows=args.block_rows)
    if args.tune:
        from repro.core.tune import autotune

        t0 = time.time()
        tuned = autotune(
            csr, k=args.batch, backend=args.backend, mode=args.tune,
            cache_dir=args.tune_cache,
        )
        print(
            f"spmv-tune: cols_per_chunk={tuned.cols_per_chunk} "
            f"block_rows={tuned.block_rows} packed={tuned.packed} "
            f"(mode={tuned.mode}, source={tuned.source}, "
            f"trials={tuned.trials}, cost={tuned.cost:.3g}, "
            f"{time.time() - t0:.3f}s)"
        )
        knobs = dict(
            window=None,
            block_rows=tuned.block_rows,
            cols_per_chunk=tuned.cols_per_chunk,
            packed=bool(tuned.packed),
        )
    t0 = time.time()
    if args.mesh:
        from repro.core.dist import ShardedSpMVEngine
        from repro.launch.mesh import parse_mesh_spec

        mesh = parse_mesh_spec(args.mesh)
        engine = ShardedSpMVEngine(
            csr,
            mesh=mesh,
            backend=args.backend,
            partition=args.partition,
            cache_dir=args.schedule_cache,
            **knobs,
        )
        # Forces every shard's schedule build.
        rep = engine.plan_report()
        plan_s = time.time() - t0
        cached = [s["schedule_cached"] for s in rep["shards"]]
        print(
            f"spmv-serve: {args.spmv} {rep['n_rows']}x{rep['n_cols']} "
            f"nnz_padded={rep['nnz_padded']} planned in {plan_s:.3f}s "
            f"(schedules_cached={sum(bool(c) for c in cached)}"
            f"/{len(cached)})"
        )
        print(
            f"  mesh: data={rep['mesh']['data']} model={rep['mesh']['model']}"
            f" ({rep['n_devices']} devices), {rep['n_shards']} row shards, "
            f"backend {rep['backend']} -> {rep['backend_resolved']}"
        )
        print(
            f"  plan: block_rows={rep['block_rows']} "
            f"wide_accesses={rep['wide_accesses']} "
            f"coalesce_rate={rep['coalesce_rate']:.2f}"
        )
        part = rep["partition"]
        imb = part["imbalance"]
        print(
            f"  partition: {part['strategy']} "
            f"imbalance={imb['ratio']:.3f} "
            f"(max={imb['max_shard_cycles']:.0f} / "
            f"mean={imb['mean_shard_cycles']:.0f} model cycles/shard)"
        )
        for s in rep["shards"]:
            print(
                f"    shard {s['shard']} [{s['device_str']}]: rows "
                f"[{s['rows'][0]}, {s['rows'][1]}) width={s['width']} "
                f"window={s['window']} "
                f"wide_accesses={s['wide_accesses']} "
                f"coalesce_rate={s['coalesce_rate']:.2f} "
                f"cached={s['schedule_cached']}"
            )
    else:
        engine = get_engine(
            csr,
            backend=args.backend,
            cache_dir=args.schedule_cache,
            **knobs,
        )
        # Forces the (lazy) schedule build.
        rep = engine.plan_report()
        plan_s = time.time() - t0
        print(
            f"spmv-serve: {args.spmv} {rep['n_rows']}x{rep['n_cols']} "
            f"nnz_padded={rep['nnz_padded']} planned in {plan_s:.3f}s "
            f"(schedule_cached={rep['schedule_cached']})"
        )
        print(
            f"  backend: {rep['backend']} -> {rep['backend_resolved']} "
            f"(cols_per_chunk={rep['cols_per_chunk']}, "
            f"plan_width={rep['plan_width']})"
        )
        print(
            f"  plan: window={rep['window']} block_rows={rep['block_rows']} "
            f"wide_accesses={rep['wide_accesses']} "
            f"coalesce_rate={rep['coalesce_rate']:.2f}"
        )
    stream_cfg = parse_stream_spec(args.stream) if args.stream else None
    streamer = None
    if stream_cfg is not None:
        streamer = StreamingExecutor(
            engine,
            microbatch=stream_cfg["microbatch"],
            depth=stream_cfg["depth"],
            # Under chaos, budget micro-batch retries so injected dispatch
            # timeouts heal inside the pipeline instead of failing batches.
            retries=2 if faults.active_plan() is not None else 0,
        )
        # The serving loop feeds every request through one pipeline, so the
        # overlap term sees the whole stream of columns, not a single batch.
        pred = engine.plan_report(
            stream={**stream_cfg, "k": args.batch * args.requests}
        )["streaming"]["perf"]["pack256"]
        hidden_side = (
            "transfer" if pred["bottleneck"] == "compute" else "compute"
        )
        print(
            f"  stream: depth={stream_cfg['depth']} "
            f"microbatch={stream_cfg['microbatch']} — model predicts "
            f"x{pred['speedup']:.3f} streamed speedup "
            f"({pred['bottleneck']}-bound, "
            f"{pred['overlap_efficiency'] * 100.0:.0f}% of {hidden_side} "
            f"hidden)"
        )
    # Host-side request batches, pregenerated so RHS generation stays out of
    # the timed loops (the host->device transfer is the thing under test).
    rng = np.random.default_rng(args.seed + 1)
    batches = [
        rng.standard_normal((csr.n_cols, args.batch)).astype(np.float32)
        for _ in range(args.requests)
    ]
    # compile/warm both paths outside the timed loops (block_until_ready is a
    # no-op on the sharded engine's host-gathered results)
    y_sync = np.asarray(jax.block_until_ready(engine.matmat(batches[0])))
    if faults.active_plan() is not None:
        # Chaos parity: the same batch computed with injection suspended is
        # the fault-free oracle; recovery must be bit-identical on the
        # reference backend and within float tolerance on pallas.
        with faults.suspended():
            y_ref = np.asarray(jax.block_until_ready(engine.matmat(batches[0])))
        chaos_err = float(np.abs(y_sync - y_ref).max()) if y_ref.size else 0.0
        chaos_tol = 0.0 if rep["backend_resolved"] == "reference" else 1e-5
        print(
            f"  chaos parity vs fault-free matmat: "
            f"max_abs_err={chaos_err:.2e} (tol={chaos_tol:g})"
        )
        if not chaos_err <= chaos_tol:
            raise SystemExit(
                f"--chaos: recovered result diverged from fault-free oracle "
                f"(max_abs_err={chaos_err:.2e} > tol={chaos_tol:g})"
            )
    if streamer is not None:
        err = float(np.abs(streamer.matmat(batches[0]) - y_sync).max())
        print(f"  stream parity vs sync matmat: max_abs_err={err:.2e}")
    t0 = time.time()
    for B in batches:
        jax.block_until_ready(engine.matmat(B))
    dt = time.time() - t0
    spmvs = args.requests * args.batch
    gflops = 2.0 * csr.nnz * spmvs / max(dt, 1e-12) / 1e9
    print(
        f"  served {args.requests} batches x {args.batch} RHS in {dt:.3f}s "
        f"sync ({spmvs / dt:.1f} SpMV/s, {gflops:.3f} GFLOP/s equivalent)"
    )
    if streamer is not None:
        t0 = time.time()
        for B in batches:
            streamer.submit(B)  # bounded in-flight queue applies backpressure
        outs = streamer.drain()
        jax.block_until_ready(list(outs))
        dt_stream = time.time() - t0
        if outs.failures:
            first = outs.failures[0]
            raise SystemExit(
                f"stream: {len(outs.failures)} batch(es) failed after "
                f"{first.retries} retry(ies): {first.error!r}"
            )
        gflops_s = 2.0 * csr.nnz * spmvs / max(dt_stream, 1e-12) / 1e9
        print(
            f"  streamed the same {args.requests} batches in {dt_stream:.3f}s "
            f"({spmvs / dt_stream:.1f} SpMV/s, {gflops_s:.3f} GFLOP/s, "
            f"x{dt / max(dt_stream, 1e-12):.2f} vs sync)"
        )
    if args.mesh:
        # Per-device throughput: each mesh device owns one (row-shard,
        # column-group) block of every batch; its share of the *real* FLOPs
        # (the shard's row range of csr.nnz — the same basis as the
        # aggregate GFLOP/s line above) over the wall time is its rate.
        per_dev = {}
        for blk in engine.placement(args.batch):
            lo, hi = blk["rows"]
            nnz_shard = int(csr.indptr[hi]) - int(csr.indptr[lo])
            c0, c1 = blk["cols"]
            flops = 2.0 * nnz_shard * (c1 - c0) * args.requests
            dev = blk["device"]
            per_dev[dev] = per_dev.get(dev, 0.0) + flops
        from repro.core.dist import device_str

        print(f"  per-device throughput ({len(per_dev)} active devices):")
        for dev in sorted(per_dev, key=lambda d: d.id):
            print(
                f"    {device_str(dev)} "
                f"{per_dev[dev] / max(dt, 1e-12) / 1e9:.3f} GFLOP/s"
            )
    stats = schedule_cache_stats()
    print(f"  schedule cache: {stats}")
    if args.assert_warm_cache:
        # CI's persistent-cache round trip: a process pointed at a warm
        # on-disk cache must not plan from scratch even once.
        if stats["built"] != 0:
            raise SystemExit(
                f"--assert-warm-cache: expected zero cold plans but "
                f"build_block_schedule ran {stats['built']} time(s) "
                f"(disk_hits={stats['disk_hits']}, "
                f"disk_rejects={stats['disk_rejects']})"
            )
        print(
            f"  warm-cache assertion OK: zero cold plans "
            f"(disk_hits={stats['disk_hits']})"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument(
        "--paged", action="store_true",
        help="serve end-to-end paged decode for --arch: per-layer paged KV "
        "caches (models.paged_kv) with the page gather resolved through the "
        "shared GatherEngine, gated on paged-vs-dense parity and zero "
        "steady-state plan builds",
    )
    ap.add_argument(
        "--decode-steps", type=int, default=16,
        help="decode steps for --paged (tokens generated per request)",
    )
    ap.add_argument(
        "--page-block", type=int, default=4,
        help="KV page size in tokens for --paged",
    )
    ap.add_argument(
        "--spmv", choices=sorted(_SPMV_MATRICES),
        help="serve batched SpMV for a synthetic matrix family instead of "
        "an LLM (routes through core.engine.SpMVEngine)",
    )
    ap.add_argument("--spmv-rows", type=int, default=8192)
    ap.add_argument(
        "--solve", choices=("cg", "pagerank", "jacobi", "power"),
        help="serve an iterative solver (core.solvers) over the --spmv "
        "matrix family instead of raw SpMV batches: the whole iteration "
        "runs in one device-resident lax.while_loop over the engine's "
        "hoisted plan (cg/jacobi SPD-ify the matrix via make_spd)",
    )
    ap.add_argument(
        "--window", type=int, default=None,
        help="coalescer window (default: 256 for the reference backend, "
        "cols_per_chunk*slice_height for pallas)",
    )
    ap.add_argument(
        "--block-rows", type=int, default=None,
        help="coalesced block size (default: 128 where the pallas matvec "
        "holds x in VMEM, else 8)",
    )
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--backend", choices=("reference", "pallas", "auto"), default="auto",
        help="SpMV execution backend (pallas runs the fused sell_spmv "
        "kernel; interpret mode off-TPU)",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="SPEC",
        help="shard --spmv serving over a device mesh: 'data,model' "
        "auto-factors all visible devices, '4,2' pins explicit (data, "
        "model) sizes; row slices shard over data, RHS columns over model "
        "(core.dist.ShardedSpMVEngine)",
    )
    ap.add_argument(
        "--partition", default="auto",
        choices=("auto", "even", "nnz", "cost", "cost2d"),
        help="row-shard partition strategy for --mesh "
        "(core.partition.shard_bounds): 'even' splits slices uniformly, "
        "'nnz' balances padded nnz, 'cost' balances the perf-model shard "
        "cost (straggler-aware; what 'auto' resolves to), 'cost2d' adds a "
        "column-segment grid to the objective",
    )
    ap.add_argument(
        "--stream", default=None, metavar="SPEC",
        help="serve --spmv through the double-buffered streaming pipeline "
        "(core.runtime.StreamingExecutor): 'depth=D,microbatch=B' (either "
        "key optional; defaults depth=2, microbatch=32) — micro-batches of "
        "B RHS columns, at most D staged-or-computing at once",
    )
    ap.add_argument(
        "--tune", nargs="?", const="model", choices=("model", "measure"),
        default=None,
        help="autotune (cols_per_chunk, block_rows, packed) for this matrix "
        "and batch width before serving (core.tune.autotune): 'model' "
        "scores candidates with the matmat cycle model, 'measure' "
        "times real matmats; winners persist content-addressed (see "
        "--tune-cache) so repeat serves run zero trials",
    )
    ap.add_argument(
        "--tune-cache", default=None, metavar="DIR",
        help="persistent tuner cache directory (default: $REPRO_TUNE_CACHE, "
        "falling back to the schedule cache directory)",
    )
    ap.add_argument(
        "--schedule-cache", default=None, metavar="DIR",
        help="persistent BlockSchedule cache directory (default: "
        "$REPRO_SCHEDULE_CACHE); cold processes load known plans from here",
    )
    ap.add_argument(
        "--assert-warm-cache", action="store_true",
        help="exit nonzero unless this process planned zero schedules from "
        "scratch (requires a warm --schedule-cache)",
    )
    ap.add_argument(
        "--chaos", default=None, metavar="SPEC",
        help="run the selected serve mode under deterministic fault "
        "injection (core.faults spec, e.g. "
        "'store_read:rate=1,count=1;shard_fail:after=1,count=1'); exits "
        "nonzero unless at least one fault was injected, every injected "
        "fault recovered, and parity with the fault-free oracle held",
    )
    args = ap.parse_args()
    enable_compile_cache()

    if args.solve and not args.spmv:
        ap.error("--solve requires --spmv to pick the matrix family")
    if not args.spmv and not args.arch:
        ap.error("--arch is required unless --spmv is given")

    if args.chaos is not None:
        try:
            plan = faults.FaultPlan(args.chaos)
        except ValueError as exc:
            ap.error(str(exc))
        with plan:
            _run_mode(args)
        rep = plan.report()
        print(
            f"chaos: spec={args.chaos!r} injected={rep['injected']} "
            f"recovered={rep['recovered']} unrecovered={rep['unrecovered']}"
        )
        for site, s in sorted(rep["sites"].items()):
            print(
                f"  {site}: events={s['events']} injected={s['injected']} "
                f"recovered={s['recovered']}"
            )
        if rep["injected"] == 0:
            raise SystemExit(
                "--chaos: spec injected no faults — nothing was exercised "
                "(check the site names / after= thresholds against this mode)"
            )
        if rep["unrecovered"]:
            raise SystemExit(
                f"--chaos: {rep['unrecovered']} injected fault(s) were not "
                f"recovered"
            )
        print("chaos: all injected faults recovered")
    else:
        _run_mode(args)


def _run_mode(args) -> None:
    """Dispatch to the serve mode the flags select (shared by the normal and
    --chaos paths so fault injection wraps exactly one mode run)."""
    if args.spmv:
        if args.solve:
            serve_solve(args)
        else:
            serve_spmv(args)
        return
    if args.paged:
        serve_paged(args)
        return

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    rt = Runtime()
    params = model.init(jax.random.PRNGKey(0))
    batch = make_input_batch(cfg, args.batch, args.prompt_len)
    t0 = time.time()
    out = generate(
        model, params, batch["tokens"], max_new_tokens=args.tokens, rt=rt,
        extras_batch=batch,
    )
    dt = time.time() - t0
    total = args.batch * args.tokens
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({total / dt:.1f} tok/s batched)")
    print(out[0][:16])


if __name__ == "__main__":
    main()
