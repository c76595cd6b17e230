"""Benchmark harness: one module per paper figure/table. Prints
``name,us_per_call,derived`` CSV rows. `BENCH_SCALE=ci|bench|paper` controls
matrix sizes (default bench). ``--smoke`` forces the tiny ci scale and runs a
quick subset (fig5 + engine cache + kernel microbench + the backend parity
gate + sharded-vs-single-device matmat) — the CI fast pass. The smoke pass
writes ``BENCH_smoke.json`` (all emitted rows, per-matrix pallas-vs-reference
max abs error, and the sharded-engine mesh/parity) and exits nonzero if any
parity error exceeds `PARITY_TOL` — CI uploads the file as a workflow
artifact (single- and multi-device variants) and fails on the gate."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

PARITY_TOL = 1e-5
SMOKE_JSON = "BENCH_smoke.json"
STREAM_JSON = "BENCH_stream.json"
MATMAT_JSON = "BENCH_matmat.json"
SOLVE_JSON = "BENCH_solve.json"
DECODE_JSON = "BENCH_decode.json"
CHAOS_JSON = "BENCH_chaos.json"
# Retrying a failed micro-batch re-stages and recomputes it, so a chaos run
# with one injected timeout costs at most one extra micro-batch plus the
# retry bookkeeping. The overhead row is informational (timings on shared CI
# CPUs drift); the *gate* is on recovery_rate and parity.
CHAOS_RETRY_BUDGET = 2
# Streamed serving must not be slower than the synchronous loop. Gated on
# the median of paired per-trial ratios (drift-cancelling); the margin
# absorbs residual CPU jitter — a real pipelining regression blows well
# past 10%.
STREAM_JITTER_TOL = 1.10
# Packed plans ship 4-byte metadata words instead of 8; the stream-level
# reduction is below 2x only because the warp tags ship either way. 1.5x is
# a conservative structural floor — it holds for any schedule whose tag
# bytes stay under half its element bytes.
PACKED_TRAFFIC_FLOOR = 1.5
# Cost-partitioned matmat must not run slower than the even split. Shard
# loops on a shared CPU host are noisier than the single-kernel timings
# above (the strict gate is the model-imbalance one), hence the wider
# margin.
PARTITION_JITTER_TOL = 1.25
# bf16 values halve the value stream but metadata and wide fetches ship at
# full width either way, so the off-chip reduction is well under 2x; any
# plan whose value bytes dominate clears 1.05 easily.
VALUE_TRAFFIC_FLOOR = 1.05
# Relative error budget for bf16-stored values (matches tests/test_bf16.py:
# bf16 keeps 8 mantissa bits; products accumulate in f32).
BF16_REL_TOL = 6e-3


def _kernel_microbench() -> None:
    """Kernel-level rows: coalesced data path vs plain gather (CPU timings are
    indicative only — the deployment target is TPU; structural metrics
    (wide-access counts) are machine-independent)."""
    import jax.numpy as jnp

    from repro.core.coalescer import coalesce_stats
    from repro.core.indirect_stream import coalesced_gather
    from .common import emit, timed

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((65536, 64)).astype(np.float32))
    # banded-like stream (high locality)
    idx = jnp.asarray(
        (np.repeat(np.arange(8192), 4) + rng.integers(0, 32, 32768))
        % 65536
    ).astype(jnp.int32)
    for backend in ("jnp", "coalesced"):
        out, us = timed(
            lambda b=backend: coalesced_gather(
                table, idx, window=256, block_rows=8, backend=b
            ).block_until_ready(),
            repeats=3,
        )
        wide, rate = coalesce_stats(np.asarray(idx), window=256, block_rows=8)
        emit(
            f"kernel/coalesced_gather/{backend}", us,
            f"n=32768;wide_accesses={wide};coalesce_rate={rate:.2f}",
        )


def _backend_parity_check() -> dict:
    """Pallas backend vs reference backend on the smoke matrices: max abs
    error per matrix. Matrices are deliberately tiny — off-TPU the kernel
    runs in interpret mode, and this is a correctness gate, not a timing."""
    import jax.numpy as jnp

    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded, powerlaw, random_uniform
    from .common import emit, timed

    smoke = (
        ("banded-512", banded(512, 16, 0.7)),
        ("powerlaw-512", powerlaw(512, 8)),
        ("random-256", random_uniform(256, 12)),
    )
    errors: dict = {}
    for name, gen in smoke:
        csr = gen(np.random.default_rng(0))
        sell = csr_to_sell(csr)
        x = jnp.asarray(
            np.random.default_rng(1).standard_normal(sell.n_cols)
            .astype(np.float32)
        )
        y_ref = np.asarray(SpMVEngine(sell, backend="reference").matvec(x))
        eng = SpMVEngine(sell, backend="pallas")
        y_pal, us = timed(lambda e=eng: e.matvec(x).block_until_ready())
        err = float(np.abs(np.asarray(y_pal) - y_ref).max())
        errors[name] = err
        emit(
            f"parity/sell_spmv_pallas/{name}", us,
            f"n={sell.n_rows};max_abs_err={err:.2e};tol={PARITY_TOL:.0e}",
        )
    return errors


def _packed_plan_smoke() -> dict:
    """Packed-metadata plan rows + the packing gates.

    For each smoke matrix, build the same pallas plan under both metadata
    encodings and report: bytes/element each encoding ships, the measured
    metadata-stream reduction (packed plans carry the warp id and the
    16-bit element offset in one int32 word), the perf model's
    mem_util/traffic-ratio under each encoding, and packed-vs-unpacked
    kernel parity for both the SpMV and the matmat path."""
    import jax.numpy as jnp

    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded, powerlaw, random_uniform
    from .common import emit

    smoke = (
        ("banded-512", banded(512, 16, 0.7)),
        ("powerlaw-512", powerlaw(512, 8)),
        ("random-256", random_uniform(256, 12)),
    )
    out: dict = {}
    for name, gen in smoke:
        csr = gen(np.random.default_rng(0))
        sell = csr_to_sell(csr)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal(sell.n_cols).astype(np.float32))
        X = jnp.asarray(
            rng.standard_normal((sell.n_cols, 8)).astype(np.float32)
        )
        packed_eng = SpMVEngine(sell, backend="pallas", packed=True)
        unpacked_eng = SpMVEngine(sell, backend="pallas", packed=False)
        meta = packed_eng.plan_report()["metadata"]
        err_mv = float(np.abs(
            np.asarray(packed_eng.matvec(x))
            - np.asarray(unpacked_eng.matvec(x))
        ).max())
        err_mm = float(np.abs(
            np.asarray(packed_eng.matmat(X))
            - np.asarray(unpacked_eng.matmat(X))
        ).max())
        emit(
            f"packed/plan/{name}", 0.0,
            f"n={sell.n_rows};bytes_per_elem={meta['meta_bytes_per_element']}"
            f";bytes_packed={meta['meta_bytes_packed']}"
            f";bytes_unpacked={meta['meta_bytes_unpacked']}"
            f";traffic_reduction={meta['traffic_reduction']:.3f}"
            f";mem_util_packed={meta['mem_util_packed']:.4f}"
            f";mem_util_unpacked={meta['mem_util_unpacked']:.4f}"
            f";parity_matvec={err_mv:.2e};parity_matmat={err_mm:.2e}",
        )
        out[name] = {
            "n": sell.n_rows,
            "packable": meta["packable"],
            "meta_bytes_per_element": meta["meta_bytes_per_element"],
            "meta_bytes_per_element_unpacked": 8,
            "meta_bytes_packed": meta["meta_bytes_packed"],
            "meta_bytes_unpacked": meta["meta_bytes_unpacked"],
            "traffic_reduction": round(meta["traffic_reduction"], 4),
            "mem_util_packed": round(meta["mem_util_packed"], 5),
            "mem_util_unpacked": round(meta["mem_util_unpacked"], 5),
            "traffic_ratio_packed": round(meta["traffic_ratio_packed"], 5),
            "traffic_ratio_unpacked": round(
                meta["traffic_ratio_unpacked"], 5
            ),
            "parity_matvec": err_mv,
            "parity_matmat": err_mm,
        }
    return out


def _packed_gate(packed: dict) -> dict:
    """Packed-plan failures, empty when clean: every smoke schedule must be
    packable and actually ship 4-byte words, the measured metadata-stream
    reduction must clear the structural floor, the model must credit the
    narrower stream with better-or-equal mem_util, and the packed kernels
    must agree with the unpacked ones within PARITY_TOL on both paths. (NaN
    comparisons are written to fail, as in the other gates.)"""
    bad = {}
    for name, row in packed.items():
        if not row["packable"]:
            bad[f"packed-{name}-packable"] = row["packable"]
        if row["meta_bytes_per_element"] != 4:
            bad[f"packed-{name}-bytes-per-elem"] = \
                row["meta_bytes_per_element"]
        if not (row["traffic_reduction"] >= PACKED_TRAFFIC_FLOOR):
            bad[f"packed-{name}-traffic-reduction"] = \
                row["traffic_reduction"]
        # packing shrinks off-chip traffic against the same ideal; mem_util
        # (achieved bandwidth) legitimately drops when compute-bound, so the
        # ordered gate is on traffic ratio, not utilization
        if not (row["traffic_ratio_packed"] <= row["traffic_ratio_unpacked"]):
            bad[f"packed-{name}-traffic-ratio"] = (
                row["traffic_ratio_packed"], row["traffic_ratio_unpacked"]
            )
        if not (row["parity_matvec"] <= PARITY_TOL):
            bad[f"packed-{name}-parity-matvec"] = row["parity_matvec"]
        if not (row["parity_matmat"] <= PARITY_TOL):
            bad[f"packed-{name}-parity-matmat"] = row["parity_matmat"]
    return bad


def _sharded_smoke() -> dict:
    """Sharded-vs-single-device matmat rows + the decomposition parity gate.

    On a single-device host the mesh degenerates to (1, 1) and the row is a
    pure overhead measurement; under the CI multi-device job
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the same code
    exercises real row-shard/column-group placement. Parity is gated either
    way: the sharded reference path must match the single-device engine (the
    decomposition is exact, so the expected error is 0.0)."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist import ShardedSpMVEngine
    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded
    from .common import emit, timed

    csr = banded(1024, 16, 0.7)(np.random.default_rng(0))
    sell = csr_to_sell(csr)
    k = 8
    X = jnp.asarray(
        np.random.default_rng(1).standard_normal((sell.n_cols, k))
        .astype(np.float32)
    )
    single = SpMVEngine(sell, backend="reference")
    _, us_single = timed(lambda: single.matmat(X).block_until_ready())
    sharded = ShardedSpMVEngine(sell, backend="reference")
    _, us_sharded = timed(lambda: jax.block_until_ready(sharded.matmat(X)))
    err = float(
        np.abs(
            np.asarray(sharded.matmat(X)) - np.asarray(single.matmat(X))
        ).max()
    )
    d, m = sharded.n_data, sharded.n_model
    emit("sharded/matmat/single_device", us_single, f"n={sell.n_rows};k={k}")
    emit(
        f"sharded/matmat/mesh_{d}x{m}", us_sharded,
        f"n={sell.n_rows};k={k};shards={sharded.n_shards};"
        f"devices={d * m};max_abs_err={err:.2e}",
    )
    return {
        "mesh": [d, m],
        "n_shards": sharded.n_shards,
        "max_abs_err": err,
    }


def _partition_smoke() -> dict:
    """Cost-balanced sharding rows on a genuinely skewed matrix.

    ``powerlaw(skew=3.0)`` clusters hub rows (crawl-ordered), so an even
    slice split leaves one straggler shard holding most of the padded nnz.
    Every strategy must stay bit-identical to the single-device engine
    (per-shard width padding reduces through the invariant tree), and the
    cost partition must beat the even split on the straggler-aware perf
    model's imbalance metric while serving matmats at least as fast."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist import ShardedSpMVEngine
    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import powerlaw
    from .common import emit, timed

    n_shards, skew, k = 4, 3.0, 8
    csr = powerlaw(2048, 6, skew=skew)(np.random.default_rng(0))
    sell = csr_to_sell(csr)
    X = jnp.asarray(
        np.random.default_rng(1).standard_normal((sell.n_cols, k))
        .astype(np.float32)
    )
    single = SpMVEngine(sell, backend="reference")
    Y0 = np.asarray(single.matmat(X))
    _, us_single = timed(lambda: single.matmat(X).block_until_ready())
    emit(
        "sharded/partition/single_device", us_single,
        f"n={sell.n_rows};k={k};skew={skew}",
    )
    out: dict = {
        "n_shards": n_shards, "skew": skew,
        "single_us": us_single, "strategies": {},
    }
    for strat in ("even", "nnz", "cost", "cost2d"):
        eng = ShardedSpMVEngine(
            sell, backend="reference", partition=strat, n_shards=n_shards
        )
        err = float(np.abs(np.asarray(eng.matmat(X)) - Y0).max())
        rep = eng.plan_report()
        part = rep["partition"]
        nnz_padded = sum(s["nnz_padded"] for s in rep["shards"])
        _, us = timed(lambda: jax.block_until_ready(eng.matmat(X)))
        emit(
            f"sharded/partition/{strat}", us,
            f"n={sell.n_rows};k={k};shards={eng.n_shards};"
            f"imbalance={part['imbalance']['ratio']:.4f};"
            f"nnz_padded={nnz_padded};max_abs_err={err:.2e}",
        )
        out["strategies"][strat] = {
            "imbalance": round(part["imbalance"]["ratio"], 5),
            "max_shard_cycles": part["imbalance"]["max_shard_cycles"],
            "mean_shard_cycles": part["imbalance"]["mean_shard_cycles"],
            "nnz_padded": nnz_padded,
            "max_abs_err": err,
            "us": us,
        }
    return out


def _partition_gate(part: dict) -> dict:
    """Partition failures, empty when clean: every strategy's sharded
    result must be bit-identical to the single-device engine, and on the
    skewed smoke matrix the cost partition must yield strictly lower
    model-cycle imbalance than the even split without serving slower.
    (NaN comparisons are written to fail, as in the other gates.)"""
    bad = {}
    strategies = part["strategies"]
    for name, row in strategies.items():
        if not (row["max_abs_err"] == 0.0):
            bad[f"partition-{name}-parity"] = row["max_abs_err"]
    even, cost = strategies["even"], strategies["cost"]
    if not (cost["imbalance"] < even["imbalance"]):
        bad["partition-cost-vs-even-imbalance"] = (
            cost["imbalance"], even["imbalance"]
        )
    if not (cost["us"] <= even["us"] * PARTITION_JITTER_TOL):
        bad["partition-cost-vs-even-throughput"] = (cost["us"], even["us"])
    return bad


def _value_dtype_smoke() -> dict:
    """bf16 SELL-value rows: the perf model must credit the halved value
    stream, and the reference engine's bf16 results must track the native
    ones within the bf16 mantissa budget (products accumulate in f32)."""
    import jax.numpy as jnp

    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded, powerlaw
    from .common import emit

    smoke = (
        ("banded-512", banded(512, 16, 0.7)),
        ("powerlaw-512", powerlaw(512, 8)),
    )
    out: dict = {}
    for name, gen in smoke:
        csr = gen(np.random.default_rng(0))
        sell = csr_to_sell(csr)
        X = jnp.asarray(
            np.random.default_rng(1).standard_normal((sell.n_cols, 8))
            .astype(np.float32)
        )
        native = SpMVEngine(sell, backend="reference")
        narrow = SpMVEngine(sell, backend="reference", value_dtype="bf16")
        vals = narrow.plan_report()["values"]
        ref = np.asarray(native.matmat(X))
        err = float(np.abs(np.asarray(narrow.matmat(X)) - ref).max())
        rel_err = err / max(float(np.abs(ref).max()), 1e-30)
        emit(
            f"values/bf16/{name}", 0.0,
            f"n={sell.n_rows};"
            f"value_bytes={vals['value_bytes_per_element']};"
            f"traffic_reduction={vals['traffic_reduction']:.3f};"
            f"rel_err={rel_err:.2e}",
        )
        out[name] = {
            "value_dtype": vals["value_dtype"],
            "value_bytes_per_element": vals["value_bytes_per_element"],
            "traffic_reduction": round(vals["traffic_reduction"], 4),
            "traffic_ratio": round(vals["traffic_ratio"], 5),
            "traffic_ratio_native": round(vals["traffic_ratio_native"], 5),
            "rel_err": rel_err,
        }
    return out


def _value_dtype_gate(values: dict) -> dict:
    """bf16 value failures, empty when clean: values must actually ship 2
    bytes, the modeled off-chip reduction must clear the structural floor
    and order correctly against native, and the numerics must stay within
    the bf16 budget. (NaN comparisons are written to fail.)"""
    bad = {}
    for name, row in values.items():
        if row["value_bytes_per_element"] != 2:
            bad[f"values-{name}-bytes-per-elem"] = \
                row["value_bytes_per_element"]
        if not (row["traffic_reduction"] >= VALUE_TRAFFIC_FLOOR):
            bad[f"values-{name}-traffic-reduction"] = \
                row["traffic_reduction"]
        if not (row["traffic_ratio"] <= row["traffic_ratio_native"]):
            bad[f"values-{name}-traffic-ratio"] = (
                row["traffic_ratio"], row["traffic_ratio_native"]
            )
        if not (row["rel_err"] <= BF16_REL_TOL):
            bad[f"values-{name}-rel-err"] = row["rel_err"]
    return bad


def _streaming_smoke() -> dict:
    """Streamed-vs-synchronous serving rows + the streaming gates.

    The serving pattern under test is the one `serve --spmv --stream` runs:
    the synchronous loop blocks on every request's matmat; the streamed loop
    submits every request into the `StreamingExecutor` pipeline (bounded
    in-flight queue) and drains once, so host->device RHS staging overlaps
    compute on the previous micro-batch. Gates: streamed output bit-identical
    to sync on the reference backend (and <= PARITY_TOL through the pallas
    backend, interpret mode off-TPU), and streamed throughput >= sync within
    `STREAM_JITTER_TOL`. Timings take the best of several trials — single
    runs on shared CI CPUs are too noisy to gate on."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist import ShardedSpMVEngine
    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded
    from repro.core.runtime import StreamingExecutor
    from .common import emit

    # Workload shape: compute per request small enough that the per-request
    # staging/dispatch overhead the pipeline hides is a measurable fraction
    # of the loop (deep matrices drown it in compute and the comparison
    # reads as a coin flip on 2-core CI runners).
    depth, microbatch, k, n_requests, trials = 2, 32, 32, 12, 15
    csr = banded(1024, 16, 0.7)(np.random.default_rng(0))
    sell = csr_to_sell(csr)
    rng = np.random.default_rng(1)
    batches = [
        rng.standard_normal((sell.n_cols, k)).astype(np.float32)
        for _ in range(n_requests)
    ]

    engine = SpMVEngine(sell, backend="reference")
    streamer = StreamingExecutor(engine, microbatch=microbatch, depth=depth)
    y_sync = np.asarray(jax.block_until_ready(engine.matmat(batches[0])))
    err_single = float(
        np.abs(np.asarray(streamer.matmat(batches[0])) - y_sync).max()
    )

    def loop_sync():
        for B in batches:
            jax.block_until_ready(engine.matmat(B))

    def loop_stream():
        for B in batches:
            streamer.submit(B)
        jax.block_until_ready(streamer.drain())

    def timed(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    # Paired trials, gated on the *median per-trial ratio*: each trial times
    # sync and streamed back to back under the same machine conditions, so
    # container-wide CPU drift (which swings absolute loop times by 30%+ on
    # shared runners) cancels out of the ratio; the median then rides out
    # the occasional trial where a noise spike hits one side of the pair.
    for fn in (loop_sync, loop_stream):
        fn()  # warm (jit of both microbatch widths, buffer pools)
    sync_times, stream_times = [], []
    for i in range(trials):
        # alternate which side runs first so thermal/cache carryover within
        # a pair cancels over the trial set too
        first, second = (
            (loop_sync, loop_stream) if i % 2 == 0
            else (loop_stream, loop_sync)
        )
        a, b = timed(first), timed(second)
        s, t = (a, b) if i % 2 == 0 else (b, a)
        sync_times.append(s)
        stream_times.append(t)
    sync_us = min(sync_times) * 1e6
    stream_us = min(stream_times) * 1e6
    speedup = float(np.median(
        [s / t for s, t in zip(sync_times, stream_times)]
    ))
    spmvs = n_requests * k
    emit(
        "stream/serve/sync", sync_us,
        f"n={sell.n_rows};k={k};requests={n_requests};"
        f"spmv_per_s={spmvs / (sync_us * 1e-6):.1f}",
    )
    emit(
        "stream/serve/streamed", stream_us,
        f"depth={depth};microbatch={microbatch};"
        f"spmv_per_s={spmvs / (stream_us * 1e-6):.1f};"
        f"speedup={speedup:.2f};max_abs_err={err_single:.2e}",
    )
    predicted = engine.plan_report(
        stream={"k": k, "microbatch": microbatch, "depth": depth}
    )["streaming"]["perf"]["pack256"]

    # Sharded engine through the same pipeline: parity is gated (the
    # decomposition plus streaming must still be bit-identical to the
    # single-device sync path); its timing row is informational — on one
    # device the mesh degenerates, under the CI streaming job it exercises
    # real 8-device placement.
    sharded = ShardedSpMVEngine(sell, backend="reference")
    sh_stream = StreamingExecutor(sharded, microbatch=microbatch, depth=depth)
    err_sharded = float(
        np.abs(np.asarray(sh_stream.matmat(batches[0])) - y_sync).max()
    )

    def loop_stream_sharded():
        for B in batches:
            sh_stream.submit(B)
        sh_stream.drain()

    loop_stream_sharded()  # warm
    sh_us = min(timed(loop_stream_sharded) for _ in range(trials)) * 1e6
    d, m = sharded.n_data, sharded.n_model
    emit(
        f"stream/serve/sharded_mesh_{d}x{m}", sh_us,
        f"depth={depth};microbatch={microbatch};shards={sharded.n_shards};"
        f"max_abs_err={err_sharded:.2e}",
    )

    # Pallas backend (interpret mode off-TPU) through the pipeline: small
    # matrix, correctness only.
    sell_small = csr_to_sell(banded(512, 16, 0.7)(np.random.default_rng(0)))
    x_small = jnp.asarray(
        np.random.default_rng(2).standard_normal((sell_small.n_cols, 8))
        .astype(np.float32)
    )
    y_ref = np.asarray(
        SpMVEngine(sell_small, backend="reference").matmat(x_small)
    )
    pal_stream = StreamingExecutor(
        SpMVEngine(sell_small, backend="pallas"), microbatch=4, depth=2
    )
    err_pallas = float(
        np.abs(np.asarray(pal_stream.matmat(x_small)) - y_ref).max()
    )
    emit(
        "stream/parity/pallas", 0.0,
        f"n={sell_small.n_rows};k=8;max_abs_err={err_pallas:.2e};"
        f"tol={PARITY_TOL:.0e}",
    )

    return {
        "depth": depth,
        "microbatch": microbatch,
        "k": k,
        "requests": n_requests,
        "trials": trials,
        "sync_us": round(sync_us, 1),
        "streamed_us": round(stream_us, 1),
        "speedup": round(speedup, 3),  # median of paired per-trial ratios
        "streamed_ge_sync": bool(speedup >= 1.0),
        "jitter_tol": STREAM_JITTER_TOL,
        "predicted_speedup_pack256": round(predicted["speedup"], 4),
        "parity": {
            "single": err_single,
            "sharded": err_sharded,
            "pallas": err_pallas,
        },
        "sharded": {
            "mesh": [d, m],
            "n_shards": sharded.n_shards,
            "streamed_us": round(sh_us, 1),
        },
    }


def _matmat_smoke() -> dict:
    """Matmat parity rows + gate: the pallas engine's `matmat` (its matvec
    batched over right-hand sides) must agree with the reference backend to
    PARITY_TOL at every tested k, and the sharded engine's decomposition
    with the single-device reference."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist import ShardedSpMVEngine
    from repro.core.engine import SpMVEngine
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded
    from .common import emit

    ks = (1, 7, 8, 32)
    csr = banded(512, 16, 0.7)(np.random.default_rng(0))
    sell = csr_to_sell(csr)
    rng = np.random.default_rng(1)
    eng = SpMVEngine(sell, backend="pallas")
    ref = SpMVEngine(sell, backend="reference")

    parity: dict = {}
    for k in ks:
        X = jnp.asarray(
            rng.standard_normal((sell.n_cols, k)).astype(np.float32)
        )
        y = np.asarray(jax.block_until_ready(eng.matmat(X)))
        y_ref = np.asarray(jax.block_until_ready(ref.matmat(X)))
        parity[str(k)] = {"pallas_vs_reference": float(np.abs(y - y_ref).max())}
        emit(
            f"matmat/parity/k{k}", 0.0,
            f"n={sell.n_rows};"
            f"pallas_vs_reference={parity[str(k)]['pallas_vs_reference']:.2e}",
        )

    # Sharded engine: every shard's matmat runs on its own device; the
    # decomposition must still match the single-device reference.
    sharded = ShardedSpMVEngine(sell, backend="pallas")
    X8 = jnp.asarray(
        rng.standard_normal((sell.n_cols, 8)).astype(np.float32)
    )
    err_sharded = float(np.abs(
        np.asarray(sharded.matmat(X8)) - np.asarray(ref.matmat(X8))
    ).max())
    d, m = sharded.n_data, sharded.n_model
    emit(
        f"matmat/sharded_mesh_{d}x{m}", 0.0,
        f"n={sell.n_rows};k=8;shards={sharded.n_shards};"
        f"max_abs_err={err_sharded:.2e}",
    )

    return {
        "ks": list(ks),
        "parity": parity,
        "sharded": {
            "mesh": [d, m],
            "n_shards": sharded.n_shards,
            "max_abs_err": err_sharded,
        },
    }


def _solve_smoke() -> dict:
    """Iterative solvers over the plan-once engine: the execute-many side
    of the paper's amortization story. Two matrix families per solver —
    CG on SPD-ified powerlaw (webbase-1M) + banded (af-shell10) sparsity,
    PageRank on the webbase-1M and wiki-talk powerlaw adjacencies — each
    solved cold (counting schedule builds) then warm (timed). Emits
    iterations/s rows and returns the gate inputs: residual correctness,
    probability-distribution checks, and the plan-reuse counters proving
    exactly one schedule build per cold solve and zero when warm."""
    import numpy as np

    from repro.core import cg, pagerank
    from repro.core.engine import clear_engine_cache, clear_schedule_cache, \
        schedule_cache_stats
    from repro.core.matrices import make_spd, suite_specs
    from .common import emit, timed

    specs = {s.name: s for s in suite_specs("ci")}
    out: dict = {"cg": {}, "pagerank": {}}

    # two sparsity families: powerlaw (webbase-1M) and banded (pwtk —
    # af-shell10's 1.5M-nnz ci instance would spend minutes in the one-time
    # plan build for no extra gate coverage)
    cg_cases = {
        "webbase-1M": make_spd(specs["webbase-1M"].gen(seed=0)),
        "pwtk": make_spd(specs["pwtk"].gen(seed=1)),
    }
    for name, csr in cg_cases.items():
        clear_engine_cache()
        clear_schedule_cache()
        b = np.random.default_rng(7).standard_normal(
            csr.n_rows
        ).astype(np.float32)
        cold = cg(csr, b, tol=1e-6, backend="reference")
        builds_cold = cold.schedule_builds
        warm, us = timed(
            lambda: cg(csr, b, tol=1e-6, backend="reference"), repeats=3
        )
        iters_per_s = warm.iterations / (us / 1e6) if us > 0 else 0.0
        # true residual recheck, independent of the solver's own counter
        dense_dot = csr.todense().astype(np.float64) @ np.asarray(
            warm.x, np.float64
        )
        true_res = float(
            np.linalg.norm(b - dense_dot) / np.linalg.norm(b)
        )
        emit(
            f"solve/cg/{name}", us,
            f"iters={warm.iterations};iters_per_s={iters_per_s:.1f};"
            f"relres={true_res:.2e};builds_cold={builds_cold};"
            f"builds_warm={warm.schedule_builds}",
        )
        out["cg"][name] = {
            "n": csr.n_rows,
            "nnz": int(csr.data.size),
            "iterations": warm.iterations,
            "iters_per_s": round(iters_per_s, 1),
            "converged": bool(warm.converged),
            "residual": warm.residual,
            "true_relres": true_res,
            "schedule_builds_cold": builds_cold,
            "schedule_builds_warm": warm.schedule_builds,
        }

    for name in ("webbase-1M", "wiki-talk"):
        clear_engine_cache()
        clear_schedule_cache()
        adj = specs[name].gen(seed=2)
        cold = pagerank(adj, tol=1e-7, backend="reference")
        builds_cold = cold.schedule_builds
        warm, us = timed(
            lambda: pagerank(adj, tol=1e-7, backend="reference"), repeats=3
        )
        iters_per_s = warm.iterations / (us / 1e6) if us > 0 else 0.0
        x = np.asarray(warm.x, np.float64)
        emit(
            f"solve/pagerank/{name}", us,
            f"iters={warm.iterations};iters_per_s={iters_per_s:.1f};"
            f"delta={warm.residual:.2e};builds_cold={builds_cold};"
            f"builds_warm={warm.schedule_builds}",
        )
        out["pagerank"][name] = {
            "n": adj.n_rows,
            "nnz": int(adj.data.size),
            "iterations": warm.iterations,
            "iters_per_s": round(iters_per_s, 1),
            "converged": bool(warm.converged),
            "l1_delta": warm.residual,
            "min_x": float(x.min()),
            "sum_x": float(x.sum()),
            "schedule_builds_cold": builds_cold,
            "schedule_builds_warm": warm.schedule_builds,
        }
    out["schedule_cache"] = schedule_cache_stats()
    return out


def _decode_smoke() -> dict:
    """Paged-decode rows + the serving-loop gates.

    The pattern under test is `launch/serve.py --paged`: a paged KV cache
    (models.paged_kv) whose page gathers resolve through the shared
    `core.gather_engine` plan cache. One decode loop checks (a) backend
    parity — the coalesced data path bit-identical to the jnp baseline,
    pallas (interpret off-TPU) and the dense `_sdpa` cache within
    PARITY_TOL — (b) plan reuse — the static page table means exactly one
    schedule build on the first step and zero across the steady state —
    and (c) shared-prefix dedup — two requests sharing prefix pages must
    produce fewer wide-block fetches than disjoint requests, through the
    same `plan_report` the serve loop prints."""
    import jax.numpy as jnp

    from repro.core.engine import (
        clear_engine_cache, clear_schedule_cache, schedule_cache_stats,
    )
    from repro.core.gather_engine import (
        clear_gather_engine_cache, gather_engine_cache_stats,
        get_gather_engine,
    )
    from repro.models.layers import _sdpa
    from repro.models.paged_kv import (
        alloc_paged, append_token, kv_plan_report, paged_attention,
    )
    from .common import emit, timed

    B, n_kv, hd, H, block, prompt, steps = 4, 2, 8, 4, 4, 8, 6
    max_len = prompt + steps
    max_pages = -(-max_len // block)
    rng = np.random.default_rng(0)
    clear_engine_cache()
    clear_schedule_cache()
    clear_gather_engine_cache()

    cache = alloc_paged(
        n_pages=B * max_pages, block=block, n_kv=n_kv, hd=hd, batch=B,
        max_len=max_len, dtype=jnp.float32,
    )
    dense_k = np.zeros((B, max_len, n_kv, hd), np.float32)
    dense_v = np.zeros((B, max_len, n_kv, hd), np.float32)

    def append(cache, pos):
        k = rng.standard_normal((B, n_kv, hd)).astype(np.float32)
        v = rng.standard_normal((B, n_kv, hd)).astype(np.float32)
        dense_k[:, pos] = k
        dense_v[:, pos] = v
        return append_token(cache, jnp.asarray(k), jnp.asarray(v))

    for pos in range(prompt):
        cache = append(cache, pos)

    # --- decode loop: every backend against the dense mirror each step
    parity = {"coalesced_vs_jnp": 0.0, "pallas_vs_dense": 0.0,
              "paged_vs_dense": 0.0}
    builds_cold = None
    for step in range(steps):
        pos = prompt + step
        cache = append(cache, pos)
        cur = pos + 1
        q = jnp.asarray(
            rng.standard_normal((B, 1, H, hd)).astype(np.float32)
        )
        out_c = np.asarray(
            paged_attention(q, cache, n_heads=H, backend="coalesced")
        )
        out_j = np.asarray(
            paged_attention(q, cache, n_heads=H, backend="jnp")
        )
        out_p = np.asarray(
            paged_attention(q, cache, n_heads=H, backend="pallas")
        )
        out_d = np.asarray(_sdpa(
            q, jnp.asarray(dense_k[:, :cur]), jnp.asarray(dense_v[:, :cur]),
            jnp.ones((B, 1, 1, cur), bool),
        ))
        parity["coalesced_vs_jnp"] = max(
            parity["coalesced_vs_jnp"], float(np.abs(out_c - out_j).max())
        )
        parity["pallas_vs_dense"] = max(
            parity["pallas_vs_dense"], float(np.abs(out_p - out_d).max())
        )
        parity["paged_vs_dense"] = max(
            parity["paged_vs_dense"], float(np.abs(out_c - out_d).max())
        )
        if step == 0:
            # all three backends share one schedule (content-addressed)
            builds_cold = schedule_cache_stats()["built"]
    builds_warm = schedule_cache_stats()["built"] - builds_cold

    # --- steady-state throughput: warm paged attention at final cache state
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
    _, us = timed(
        lambda: paged_attention(
            q, cache, n_heads=H, backend="coalesced"
        ).block_until_ready(),
        repeats=5,
    )
    tok_per_s = B / (us * 1e-6)
    rep = kv_plan_report(cache)
    emit(
        "decode/steady_state", us,
        f"batch={B};len={max_len};tok_per_s={tok_per_s:.1f};"
        f"builds_cold={builds_cold};builds_warm={builds_warm};"
        f"wide_accesses={rep['wide_accesses']}",
    )
    for name, err in parity.items():
        emit(
            f"decode/parity/{name}", 0.0,
            f"max_abs_err={err:.2e};tol={PARITY_TOL:.0e}",
        )

    # --- shared-prefix dedup vs disjoint requests, through the same engine
    # plan_report the serve loop prints (a page row is 256 f32 = 1KB)
    Bp, priv, shared_n = 8, 4, 4
    shared_tbl = np.stack([
        np.concatenate([
            np.arange(shared_n), shared_n + b * priv + np.arange(priv),
        ])
        for b in range(Bp)
    ]).astype(np.int32)
    disjoint_tbl = (
        np.arange(Bp)[:, None] * (shared_n + priv)
        + np.arange(shared_n + priv)[None, :]
    ).astype(np.int32)
    n_rows = Bp * (shared_n + priv)
    window = Bp * (shared_n + priv)
    rep_shared = get_gather_engine(
        (n_rows, 256), shared_tbl.reshape(-1),
        window=window, block_rows=1, backend="coalesced",
    ).plan_report()
    rep_disjoint = get_gather_engine(
        (n_rows, 256), disjoint_tbl.reshape(-1),
        window=window, block_rows=1, backend="coalesced",
    ).plan_report()
    dedup = {
        "requests": Bp,
        "shared_prefix_pages": shared_n,
        "private_pages": priv,
        "shared_wide": rep_shared["wide_accesses"],
        "disjoint_wide": rep_disjoint["wide_accesses"],
        "dedup_ratio": rep_disjoint["wide_accesses"]
        / rep_shared["wide_accesses"],
        "shared_coalesce_rate": round(rep_shared["coalesce_rate"], 4),
        "model_speedup_shared": round(
            rep_shared["gather_perf"]["speedup"], 4
        ),
        "model_speedup_disjoint": round(
            rep_disjoint["gather_perf"]["speedup"], 4
        ),
    }
    emit(
        "decode/shared_prefix", 0.0,
        f"requests={Bp};shared_wide={dedup['shared_wide']};"
        f"disjoint_wide={dedup['disjoint_wide']};"
        f"dedup_ratio={dedup['dedup_ratio']:.2f};"
        f"model_speedup={dedup['model_speedup_shared']}",
    )

    return {
        "batch": B,
        "prompt": prompt,
        "steps": steps,
        "page_block": block,
        "max_len": max_len,
        "parity": parity,
        "schedule_builds_cold": builds_cold,
        "schedule_builds_warm": builds_warm,
        "steady_state_us": round(us, 1),
        "tokens_per_s": round(tok_per_s, 1),
        "plan": {
            "wide_accesses": rep["wide_accesses"],
            "coalesce_rate": round(rep["coalesce_rate"], 4),
            "meta_bytes_per_element":
                rep["metadata"]["meta_bytes_per_element"],
        },
        "shared_prefix": dedup,
        "gather_engine_cache": gather_engine_cache_stats(),
    }


def _decode_gate(decode: dict) -> dict:
    """Paged-decode failures, empty when clean: the coalesced data path must
    be bit-identical to the jnp gather, pallas and the paged cache itself
    within PARITY_TOL of the dense reference; exactly one schedule build on
    the cold step and zero in the steady state; shared-prefix requests must
    fetch strictly fewer wide blocks than disjoint ones. (NaN comparisons
    are written to fail, as in the other gates.)"""
    bad = {}
    if not (decode["parity"]["coalesced_vs_jnp"] == 0.0):
        bad["decode-coalesced-vs-jnp"] = decode["parity"]["coalesced_vs_jnp"]
    if not (decode["parity"]["pallas_vs_dense"] <= PARITY_TOL):
        bad["decode-pallas-parity"] = decode["parity"]["pallas_vs_dense"]
    if not (decode["parity"]["paged_vs_dense"] <= PARITY_TOL):
        bad["decode-paged-vs-dense"] = decode["parity"]["paged_vs_dense"]
    if decode["schedule_builds_cold"] != 1:
        bad["decode-plan-cold"] = decode["schedule_builds_cold"]
    if decode["schedule_builds_warm"] != 0:
        bad["decode-plan-warm"] = decode["schedule_builds_warm"]
    sp = decode["shared_prefix"]
    if not (sp["shared_wide"] < sp["disjoint_wide"]):
        bad["decode-shared-prefix-dedup"] = (
            sp["shared_wide"], sp["disjoint_wide"]
        )
    if not (sp["dedup_ratio"] > 1.0):
        bad["decode-dedup-ratio"] = sp["dedup_ratio"]
    return bad


def _chaos_smoke() -> dict:
    """Deterministic fault-injection drills through `core.faults` + the
    recovery machinery each one gates.

    Four drills, every one comparing the chaos run against its fault-free
    oracle on the reference backend (bit-identical is the contract):

      * store corruption — a warm on-disk schedule is corrupted before the
        cold read; the store must quarantine (``*.bad``), rebuild, and
        re-persist, and the rebuilt plan must serve identical results.
      * store write — two injected transient ENOSPC errors inside the atomic
        write; bounded retry must land the file anyway.
      * streaming retry — an injected micro-batch dispatch timeout healed by
        `StreamingExecutor(retries=...)`; the overhead row measures the
        retry cost against a clean streamed run of the same workload.
      * sharded degraded mode — an injected shard dispatch failure recovered
        by the reference recompute path.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core import faults
    from repro.core.dist import ShardedSpMVEngine
    from repro.core.engine import (
        clear_engine_cache, clear_schedule_cache, get_engine,
        schedule_cache_stats,
    )
    from repro.core.formats import csr_to_sell
    from repro.core.matrices import banded
    from repro.core.runtime import StreamingExecutor
    from .common import emit

    csr = banded(1024, 16, 0.7)(np.random.default_rng(0))
    sell = csr_to_sell(csr)
    rng = np.random.default_rng(1)
    X = jnp.asarray(
        rng.standard_normal((sell.n_cols, 8)).astype(np.float32)
    )
    out: dict = {}

    # --- store corruption: quarantine + rebuild, cold-start parity
    cache_dir = tempfile.mkdtemp(prefix="bench-chaos-")
    try:
        clear_engine_cache()
        clear_schedule_cache()
        eng = get_engine(sell, backend="reference", cache_dir=cache_dir)
        y_free = np.asarray(eng.matmat(X))  # warms the disk cache
        clear_engine_cache()
        clear_schedule_cache()
        with faults.FaultPlan("store_read:rate=1,count=1") as plan:
            eng2 = get_engine(sell, backend="reference", cache_dir=cache_dir)
            y_chaos = np.asarray(eng2.matmat(X))
        stats = schedule_cache_stats()
        rep = plan.report()
        err = float(np.abs(y_chaos - y_free).max())
        # third cold start: the rebuilt file must serve a clean warm hit
        clear_engine_cache()
        clear_schedule_cache()
        eng3 = get_engine(sell, backend="reference", cache_dir=cache_dir)
        err_rebuilt = float(np.abs(np.asarray(eng3.matmat(X)) - y_free).max())
        rebuilt_hits = schedule_cache_stats()["disk_hits"]
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    out["store_read"] = {
        "injected": rep["injected"],
        "recovered": rep["recovered"],
        "quarantined": stats["quarantined"],
        "rebuilds": stats["rebuilds"],
        "max_abs_err": err,
        "rebuilt_cold_start_err": err_rebuilt,
        "rebuilt_disk_hits": rebuilt_hits,
    }
    emit(
        "chaos/store_read/quarantine_rebuild", 0.0,
        f"n={sell.n_rows};injected={rep['injected']};"
        f"recovered={rep['recovered']};quarantined={stats['quarantined']};"
        f"rebuilds={stats['rebuilds']};max_abs_err={err:.2e}",
    )

    # --- store write: transient ENOSPC absorbed by bounded retry
    cache_dir = tempfile.mkdtemp(prefix="bench-chaos-")
    try:
        clear_engine_cache()
        clear_schedule_cache()
        with faults.FaultPlan("store_write:rate=1,count=2") as plan:
            eng = get_engine(sell, backend="reference", cache_dir=cache_dir)
            eng.plan_report()  # forces plan + write-through save
        stats = schedule_cache_stats()
        rep = plan.report()
        saved = stats["disk_saves"] == 1 and stats["save_errors"] == 0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    out["store_write"] = {
        "injected": rep["injected"],
        "recovered": rep["recovered"],
        "retries": stats["retries"],
        "saved": bool(saved),
    }
    emit(
        "chaos/store_write/retry", 0.0,
        f"injected={rep['injected']};recovered={rep['recovered']};"
        f"retries={stats['retries']};saved={saved}",
    )

    # --- streaming retry: injected dispatch timeout + the overhead row
    clear_engine_cache()
    clear_schedule_cache()
    engine = get_engine(sell, backend="reference")
    n_requests, microbatch = 8, 4
    batches = [
        rng.standard_normal((sell.n_cols, 8)).astype(np.float32)
        for _ in range(n_requests)
    ]
    y_expect = [np.asarray(engine.matmat(B)) for B in batches]
    streamer = StreamingExecutor(
        engine, microbatch=microbatch, depth=2, retries=CHAOS_RETRY_BUDGET
    )

    def loop() -> list:
        for B in batches:
            streamer.submit(B)
        outs = streamer.drain()
        jax.block_until_ready(list(outs))
        return outs

    loop()  # warm
    t0 = time.perf_counter()
    loop()
    clean_us = (time.perf_counter() - t0) * 1e6
    with faults.FaultPlan("dispatch_timeout:after=3,count=2") as plan:
        t0 = time.perf_counter()
        outs = loop()
        chaos_us = (time.perf_counter() - t0) * 1e6
    rep = plan.report()
    err_stream = max(
        float(np.abs(np.asarray(y) - y_expect[i]).max())
        for i, y in enumerate(outs)
    )
    overhead = chaos_us / max(clean_us, 1e-9)
    out["stream_retry"] = {
        "injected": rep["injected"],
        "recovered": rep["recovered"],
        "retries": streamer.stats["retries"],
        "failures": len(outs.failures),
        "max_abs_err": err_stream,
        "clean_us": round(clean_us, 1),
        "chaos_us": round(chaos_us, 1),
        "retry_overhead": round(overhead, 3),
    }
    emit("chaos/stream/clean", clean_us,
         f"requests={n_requests};microbatch={microbatch}")
    emit(
        "chaos/stream/retry", chaos_us,
        f"injected={rep['injected']};recovered={rep['recovered']};"
        f"retries={streamer.stats['retries']};"
        f"overhead={overhead:.2f};max_abs_err={err_stream:.2e}",
    )

    # --- sharded degraded mode: shard failure -> reference recompute
    sharded = ShardedSpMVEngine(sell, backend="reference")
    y_free = np.asarray(sharded.matmat(X))
    with faults.FaultPlan("shard_fail:rate=1,count=1") as plan:
        y_chaos = np.asarray(sharded.matmat(X))
    rep = plan.report()
    rec = sharded.recovery_report()
    err_shard = float(np.abs(y_chaos - y_free).max())
    out["shard_fail"] = {
        "injected": rep["injected"],
        "recovered": rep["recovered"],
        "recovery_events": rec["recovered"],
        "max_abs_err": err_shard,
        "mesh": [sharded.n_data, sharded.n_model],
        "n_shards": sharded.n_shards,
    }
    emit(
        "chaos/shard_fail/degraded_mode", 0.0,
        f"shards={sharded.n_shards};injected={rep['injected']};"
        f"recovered={rep['recovered']};max_abs_err={err_shard:.2e}",
    )

    injected = sum(d["injected"] for d in out.values())
    recovered = sum(d["recovered"] for d in out.values())
    out["totals"] = {
        "injected": injected,
        "recovered": recovered,
        "recovery_rate": recovered / injected if injected else 0.0,
    }
    emit(
        "chaos/totals", 0.0,
        f"injected={injected};recovered={recovered};"
        f"recovery_rate={out['totals']['recovery_rate']:.2f}",
    )
    return out


def _chaos_gate(chaos: dict) -> dict:
    """Chaos failures, empty when clean: every drill must inject at least
    one fault, recover every injected fault, and stay bit-identical to its
    fault-free oracle on the reference backend; the corrupted file must be
    quarantined exactly once and rebuilt, the retried write must land, and
    the streamed pipeline must report zero failed batches. (NaN comparisons
    are written to fail, as in the other gates.)"""
    bad = {}
    for drill in ("store_read", "store_write", "stream_retry", "shard_fail"):
        row = chaos[drill]
        if row["injected"] < 1:
            bad[f"chaos-{drill}-injected"] = row["injected"]
        if row["recovered"] != row["injected"]:
            bad[f"chaos-{drill}-unrecovered"] = (
                row["injected"], row["recovered"]
            )
        if "max_abs_err" in row and not (row["max_abs_err"] == 0.0):
            bad[f"chaos-{drill}-parity"] = row["max_abs_err"]
    sr = chaos["store_read"]
    if sr["quarantined"] != 1 or sr["rebuilds"] != 1:
        bad["chaos-store-read-heal"] = (sr["quarantined"], sr["rebuilds"])
    if not (sr["rebuilt_cold_start_err"] == 0.0):
        bad["chaos-store-read-rebuilt-parity"] = sr["rebuilt_cold_start_err"]
    if sr["rebuilt_disk_hits"] != 1:
        bad["chaos-store-read-rebuilt-hits"] = sr["rebuilt_disk_hits"]
    if not chaos["store_write"]["saved"]:
        bad["chaos-store-write-saved"] = False
    if chaos["stream_retry"]["failures"] != 0:
        bad["chaos-stream-failures"] = chaos["stream_retry"]["failures"]
    if not (chaos["totals"]["recovery_rate"] == 1.0):
        bad["chaos-recovery-rate"] = chaos["totals"]["recovery_rate"]
    return bad


def _solve_gate(solve: dict) -> dict:
    """Solver failures, empty when clean: CG must converge with the
    independently recomputed relative residual under 10x its tolerance;
    PageRank must converge to a probability distribution; every cold solve
    builds exactly one schedule and every warm solve builds none. (NaN
    comparisons are written to fail, as in the other gates.)"""
    bad = {}
    for name, row in solve["cg"].items():
        if not row["converged"]:
            bad[f"solve-cg-{name}-converged"] = row["residual"]
        if not (row["true_relres"] <= 1e-5):
            bad[f"solve-cg-{name}-residual"] = row["true_relres"]
    for name, row in solve["pagerank"].items():
        if not row["converged"]:
            bad[f"solve-pagerank-{name}-converged"] = row["l1_delta"]
        if not (row["min_x"] >= -1e-9):
            bad[f"solve-pagerank-{name}-nonneg"] = row["min_x"]
        if not (abs(row["sum_x"] - 1.0) <= 1e-5):
            bad[f"solve-pagerank-{name}-mass"] = row["sum_x"]
    for solver in ("cg", "pagerank"):
        for name, row in solve[solver].items():
            if row["schedule_builds_cold"] != 1:
                bad[f"solve-{solver}-{name}-plan-cold"] = \
                    row["schedule_builds_cold"]
            if row["schedule_builds_warm"] != 0:
                bad[f"solve-{solver}-{name}-plan-warm"] = \
                    row["schedule_builds_warm"]
    return bad


def _matmat_gate(matmat: dict) -> dict:
    """Matmat failures, empty when clean: parity within PARITY_TOL at every
    k and on the sharded engine (NaN comparisons are written to fail, as in
    the other gates)."""
    bad = {}
    for k, errs in matmat["parity"].items():
        for name, err in errs.items():
            if not (err <= PARITY_TOL):
                bad[f"matmat-parity-k{k}-{name}"] = err
    if not (matmat["sharded"]["max_abs_err"] <= PARITY_TOL):
        bad["matmat-sharded-parity"] = matmat["sharded"]["max_abs_err"]
    return bad


def _stream_gate(stream: dict) -> dict:
    """Streaming failures, empty when clean: reference parity must be exact,
    pallas within PARITY_TOL, and the median paired streamed-vs-sync ratio
    must stay within the jitter tolerance of >= 1. (NaN comparisons are
    written to fail, as in the smoke gate.)"""
    bad = {}
    if not (stream["parity"]["single"] == 0.0):
        bad["stream-single-parity"] = stream["parity"]["single"]
    if not (stream["parity"]["sharded"] == 0.0):
        bad["stream-sharded-parity"] = stream["parity"]["sharded"]
    if not (stream["parity"]["pallas"] <= PARITY_TOL):
        bad["stream-pallas-parity"] = stream["parity"]["pallas"]
    if not (stream["speedup"] * STREAM_JITTER_TOL >= 1.0):
        bad["stream-throughput"] = stream["speedup"]
    return bad


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="quick CI pass: ci-scale matrices, fig5 + engine cache + kernels",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="streamed-vs-sync serving rows through "
        "core.runtime.StreamingExecutor; writes BENCH_stream.json and gates "
        "parity + streamed>=sync throughput (implies ci scale)",
    )
    ap.add_argument(
        "--matmat", action="store_true",
        help="matmat parity rows, pallas against the reference backend; "
        "writes BENCH_matmat.json and gates parity (1e-5 at every k and on "
        "the sharded engine; implies ci scale)",
    )
    ap.add_argument(
        "--solve", action="store_true",
        help="iterative-solver rows (CG + PageRank over two matrix "
        "families) through core.solvers; writes BENCH_solve.json and gates "
        "residual correctness, the PageRank probability distribution, and "
        "plan reuse (exactly one schedule build per cold solve, zero warm; "
        "implies ci scale)",
    )
    ap.add_argument(
        "--decode", action="store_true",
        help="paged-decode serving rows (models.paged_kv through the shared "
        "core.gather_engine plan cache); writes BENCH_decode.json and gates "
        "backend/dense parity, plan reuse (one cold schedule build, zero "
        "steady-state), and shared-prefix wide-fetch dedup vs disjoint "
        "requests (implies ci scale)",
    )
    ap.add_argument(
        "--chaos", action="store_true",
        help="deterministic fault-injection drills (core.faults) through "
        "the self-healing store, streaming retry, and sharded degraded "
        "mode; writes BENCH_chaos.json and gates 100%% recovery plus "
        "bit-identical parity with each drill's fault-free oracle "
        "(implies ci scale)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = (
        args.smoke or args.stream or args.matmat or args.solve
        or args.decode or args.chaos
    )
    if quick:
        os.environ["BENCH_SCALE"] = "ci"  # before .common reads it

    t0 = time.time()
    from . import common, engine_cache, fig5_spmv

    print("name,us_per_call,derived")
    if quick:
        parity: dict = {}
        sharded = None
        packed_plans = None
        partition = None
        value_dtypes = None
        if args.smoke:
            fig5_spmv.run()
            engine_cache.run()
            _kernel_microbench()
            parity = _backend_parity_check()
            packed_plans = _packed_plan_smoke()
            sharded = _sharded_smoke()
            partition = _partition_smoke()
            value_dtypes = _value_dtype_smoke()
        stream = _streaming_smoke() if args.stream else None
        matmat = _matmat_smoke() if args.matmat else None
        solve = _solve_smoke() if args.solve else None
        decode = _decode_smoke() if args.decode else None
        chaos = _chaos_smoke() if args.chaos else None
        total_s = time.time() - t0
        bad = {k: v for k, v in parity.items() if not (v <= PARITY_TOL)}
        if args.smoke:
            from repro.core.engine import engine_cache_stats, \
                schedule_cache_stats

            payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "total_s": round(total_s, 1),
                "parity_tol": PARITY_TOL,
                "backend_parity": parity,
                "packed_plans": packed_plans,
                "sharded": sharded,
                "sharded_partition": partition,
                "value_dtypes": value_dtypes,
                # The caches this pass observed: regressions in plan reuse
                # (built creeping above the matrix count, disk_rejects,
                # engine-cache misses on repeat lookups) show up in the perf
                # trajectory artifact, not just as test failures.
                "cache": {
                    "schedule": schedule_cache_stats(),
                    "engine": engine_cache_stats(),
                },
                "rows": common.rows(),
            }
            with open(SMOKE_JSON, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {SMOKE_JSON} ({len(payload['rows'])} rows)")
            # NaN must fail too, hence the negated <= rather than a >.
            if not (sharded["max_abs_err"] <= PARITY_TOL):
                bad["sharded-vs-single-device"] = sharded["max_abs_err"]
            bad.update(_packed_gate(packed_plans))
            bad.update(_partition_gate(partition))
            bad.update(_value_dtype_gate(value_dtypes))
        if stream is not None:
            stream_payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "parity_tol": PARITY_TOL,
                "stream": stream,
                "rows": [
                    r for r in common.rows() if r["name"].startswith("stream/")
                ],
            }
            with open(STREAM_JSON, "w") as f:
                json.dump(stream_payload, f, indent=2)
            print(f"# wrote {STREAM_JSON} (speedup {stream['speedup']:.2f})")
            bad.update(_stream_gate(stream))
        if matmat is not None:
            matmat_payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "parity_tol": PARITY_TOL,
                "matmat": matmat,
                "rows": [
                    r for r in common.rows() if r["name"].startswith("matmat/")
                ],
            }
            with open(MATMAT_JSON, "w") as f:
                json.dump(matmat_payload, f, indent=2)
            print(f"# wrote {MATMAT_JSON}")
            bad.update(_matmat_gate(matmat))
        if solve is not None:
            solve_payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "solve": solve,
                "rows": [
                    r for r in common.rows() if r["name"].startswith("solve/")
                ],
            }
            with open(SOLVE_JSON, "w") as f:
                json.dump(solve_payload, f, indent=2)
            print(
                f"# wrote {SOLVE_JSON} "
                f"({len(solve['cg'])} cg + {len(solve['pagerank'])} "
                f"pagerank cases)"
            )
            bad.update(_solve_gate(solve))
        if decode is not None:
            decode_payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "parity_tol": PARITY_TOL,
                "decode": decode,
                "rows": [
                    r for r in common.rows() if r["name"].startswith("decode/")
                ],
            }
            with open(DECODE_JSON, "w") as f:
                json.dump(decode_payload, f, indent=2)
            print(
                f"# wrote {DECODE_JSON} ({decode['tokens_per_s']:.1f} tok/s, "
                f"dedup_ratio {decode['shared_prefix']['dedup_ratio']:.2f})"
            )
            bad.update(_decode_gate(decode))
        if chaos is not None:
            chaos_payload = {
                "scale": os.environ.get("BENCH_SCALE", "ci"),
                "chaos": chaos,
                "rows": [
                    r for r in common.rows() if r["name"].startswith("chaos/")
                ],
            }
            with open(CHAOS_JSON, "w") as f:
                json.dump(chaos_payload, f, indent=2)
            print(
                f"# wrote {CHAOS_JSON} "
                f"({chaos['totals']['injected']} faults injected, "
                f"recovery_rate {chaos['totals']['recovery_rate']:.2f})"
            )
            bad.update(_chaos_gate(chaos))
        print(f"# total {total_s:.1f}s (smoke)")
        if bad:
            print(
                f"# GATE FAILURE on {sorted(bad)}: {bad}",
                file=sys.stderr,
            )
            raise SystemExit(1)
        return

    from . import fig3_indirect_stream, fig4_breakdown, fig6_efficiency

    fig3_indirect_stream.run()
    fig4_breakdown.run()
    fig5_spmv.run()
    fig6_efficiency.run()
    engine_cache.run()
    _kernel_microbench()
    try:
        from . import roofline

        roofline.run()
    except Exception as e:  # dry-run artifacts may not exist yet
        print(f"roofline/skipped,0.0,reason={type(e).__name__}")
    print(f"# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
