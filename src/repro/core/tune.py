"""Plan autotuner: search (cols_per_chunk, block_rows, packed, value_dtype)
per matrix.

The pallas plan has coupled knobs and no hand-pickable sweet spot:
`cols_per_chunk` sets both the coalescing window (``cols_per_chunk *
slice_height``) *and* the width padding the plan pays for, and `block_rows`
sets the wide-fetch granularity (wider blocks coalesce more but waste bytes
on sparse hits). This module searches the cross product per matrix and
remembers the winner:

  * ``mode="model"`` (default) scores every candidate with
    `perfmodel.plan_matmat_cycles` at a one-column tile — k single-column
    passes, as `SpMVEngine.matmat` runs them — evaluated on the candidate's
    *own* plan geometry (its padded stream, its window, its block
    granularity). Pure numpy on the index stream: no compilation, no
    device, deterministic.
  * ``mode="measure"`` builds each candidate engine through `get_engine`
    (so trial engines land in the engine cache warm) and times real
    ``matmat`` calls, interleaved round-robin across candidates so shared-
    machine drift cancels out of the comparison instead of crowning whoever
    ran during a quiet spell.
  * Winners persist content-addressed next to the schedule store: JSON files
    keyed on the matrix content digest + search parameters, under
    ``$REPRO_TUNE_CACHE`` (or the schedule cache directory when only that is
    configured — one cache tree for everything plan-shaped). A cold process
    re-tuning a known matrix runs **zero** trials; tampered or stale files
    are rejected and re-searched, mirroring `core.schedule_store`.

`get_tuned_engine` closes the loop: autotune, then feed the winning knobs
straight into `get_engine` (``serve --spmv --tune`` is the CLI surface).
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple, Union

import numpy as np

from . import faults, schedule_store
from .coalescer import META_BYTES_PACKED, META_BYTES_UNPACKED
from .engine import SpMVEngine, VALUE_DTYPES, _sell_content_digest, \
    get_engine, resolve_backend, resolve_value_dtype, value_bytes_per_elem
from .formats import CSRMatrix, SELLMatrix
from .perfmodel import DEFAULT_HW, HWConfig, plan_matmat_cycles
from .runtime import normalize_to_sell, pad_width

TUNE_CACHE_ENV = "REPRO_TUNE_CACHE"
TUNE_VERSION = 4  # v4: the RHS tile and the pipeline depth left the
# space (v3: value_dtype joined it; v2: packed and the depth); winners of
# another question are deliberately re-searched

# The search space: every combination is a legal plan (cols_per_chunk widens
# the window and the width padding together; block_rows is the wide-fetch
# granularity; packed toggles the 4-byte metadata encoding; value_dtype the
# SELL value storage width — bf16 halves the value stream at a numerics
# cost the caller owns). Deliberately small — the tuner is rerun per
# matrix, and the persisted winner makes even the model-mode search a
# one-time cost.
DEFAULT_SPACE: Dict[str, Tuple] = {
    "cols_per_chunk": (4, 8, 16),
    "block_rows": (4, 8, 16),
    "packed": (0, 1),
    "value_dtype": ("native", "bf16"),
}
TUNE_MODES = ("model", "measure")


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One search winner. ``trials`` counts the candidate evaluations *this
    call* ran (0 on any cache hit — the roundtrip guarantee CI pins);
    ``source`` says where the winner came from ('search' | 'memory' |
    'disk')."""

    cols_per_chunk: int
    block_rows: int
    packed: int  # 0 | 1 — int (not bool) so the space/JSON stay uniform
    value_dtype: str  # 'native' | 'bf16' | 'f32' (engine.VALUE_DTYPES)
    k: int
    backend: str  # resolved
    mode: str
    cost: float  # model cycles (mode='model') or best measured us
    trials: int
    source: str


_memory: Dict[str, TunedPlan] = {}
_lock = threading.Lock()
_stats = {
    "searched": 0, "trials": 0, "memory_hits": 0, "disk_hits": 0,
    "disk_rejects": 0, "disk_saves": 0,
    # Self-healing counters, mirroring the schedule store: rejected winner
    # files are quarantined (`*.bad`) and re-searched; transient IO errors on
    # the atomic write are retried with backoff; a write that stays broken
    # degrades to memory-only instead of failing the search.
    "quarantined": 0, "retries": 0, "save_errors": 0,
}


def tune_stats() -> Dict[str, int]:
    with _lock:
        return dict(_stats)


def clear_tune_cache() -> None:
    """Empty the in-memory tune cache and zero the counters (on-disk files
    are untouched — the cross-process cache is the point)."""
    with _lock:
        _memory.clear()
        for key in _stats:
            _stats[key] = 0


def _bump(counter: str, by: int = 1) -> None:
    with _lock:
        _stats[counter] += by


def resolve_tune_cache_dir(cache_dir: Optional[str]) -> Optional[str]:
    """Explicit directory wins; else ``$REPRO_TUNE_CACHE``; else the schedule
    store's directory (``$REPRO_SCHEDULE_CACHE``) so tuned plans live next to
    the schedules they shape; else None (persistence off)."""
    if cache_dir is not None:
        return str(cache_dir)
    env = os.environ.get(TUNE_CACHE_ENV) or None
    if env is not None:
        return env
    return schedule_store.resolve_cache_dir(None)


def _normalize_space(
    space: Optional[Dict[str, Iterable[int]]]
) -> Dict[str, Tuple[int, ...]]:
    space = dict(DEFAULT_SPACE) if space is None else dict(space)
    unknown = set(space) - set(DEFAULT_SPACE)
    if unknown:
        raise ValueError(
            f"unknown tune-space knobs {sorted(unknown)}; valid: "
            f"{sorted(DEFAULT_SPACE)}"
        )
    out: Dict[str, Tuple] = {}
    for knob in DEFAULT_SPACE:
        raw = space.get(knob, DEFAULT_SPACE[knob])
        if knob == "value_dtype":
            values = tuple(sorted({str(v) for v in raw}))
            if not values or any(v not in VALUE_DTYPES for v in values):
                raise ValueError(
                    f"tune-space knob 'value_dtype' must list strings in "
                    f"{VALUE_DTYPES}, got {values}")
            out[knob] = values
            continue
        values = tuple(sorted({int(v) for v in raw}))
        if knob == "packed":
            if not values or any(v not in (0, 1) for v in values):
                raise ValueError(
                    f"tune-space knob 'packed' must list ints in (0, 1), "
                    f"got {values}")
        elif not values or any(v < 1 for v in values):
            raise ValueError(f"tune-space knob {knob!r} must list ints >= 1, "
                             f"got {values}")
        out[knob] = values
    return out


def _candidates(space: Dict[str, Tuple[int, ...]]) -> List[Dict[str, int]]:
    knobs = sorted(space)
    return [
        dict(zip(knobs, combo))
        for combo in itertools.product(*(space[k] for k in knobs))
    ]


def tune_key(
    matrix_digest: str, *, k: int, backend: str, mode: str,
    space: Dict[str, Tuple[int, ...]],
    hw: HWConfig = DEFAULT_HW,
    rounds: Optional[int] = None,
) -> str:
    """Filename-safe digest of the search identity: same matrix + same
    question -> same persisted winner. The question includes everything that
    changes the objective: k, backend, mode, the search space, the hardware
    model (a custom `hw` must not hit a DEFAULT_HW winner), and — for
    measured searches only — the trial count."""
    payload = repr((
        TUNE_VERSION, matrix_digest, int(k), backend, mode,
        tuple(sorted((knob, space[knob]) for knob in space)),
        tuple(sorted(dataclasses.asdict(hw).items())),
        None if rounds is None else int(rounds),
    ))
    return hashlib.sha256(payload.encode()).hexdigest()[:32]


def tune_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"tune-{key}.json")


def _save(path: str, plan: TunedPlan, *, matrix_digest: str, key: str) -> None:
    payload = {
        "version": TUNE_VERSION,
        "matrix_digest": matrix_digest,
        "key": key,
        "winner": dataclasses.asdict(plan),
    }
    dirname = os.path.dirname(path) or "."
    os.makedirs(dirname, exist_ok=True)
    blob = json.dumps(payload, indent=2).encode()
    try:
        schedule_store.retry_io(
            lambda: schedule_store.atomic_write_bytes(
                path, lambda f: f.write(blob), suffix=".json.tmp"
            ),
            what=f"save tuned plan {path}",
            on_retry=lambda: _bump("retries"),
        )
    except OSError:
        _bump("save_errors")
        return
    _bump("disk_saves")


def _load(
    path: str, *, matrix_digest: str, key: str,
    space: Dict[str, Tuple[int, ...]], k: int, backend: str, mode: str,
) -> Optional[TunedPlan]:
    """Load a persisted winner; any mismatch counts as a miss — rejected
    files are re-searched, never trusted. Beyond the header (version,
    digest, key), the winner body itself is validated against the search it
    claims to answer: every knob must come from the keyed space, and
    k/backend/mode/cost must be the question's own — a hand-edited winner
    must not smuggle knobs the search never produced into `get_engine`.

    Self-healing: transient IO errors retry with backoff, and a rejected
    file is quarantined (renamed ``*.bad``) so the re-search that follows
    can persist a fresh winner instead of fighting the broken bytes."""
    faults.corrupt_file(path, "store_read")

    def _read():
        with open(path) as f:
            return json.load(f)

    try:
        payload = schedule_store.retry_io(
            _read, what=f"load tuned plan {path}",
            on_retry=lambda: _bump("retries"),
        )
        if (
            payload.get("version") != TUNE_VERSION
            or payload.get("matrix_digest") != matrix_digest
            or payload.get("key") != key
        ):
            raise ValueError("header mismatch")
        w = payload["winner"]
        plan = TunedPlan(
            cols_per_chunk=int(w["cols_per_chunk"]),
            block_rows=int(w["block_rows"]),
            packed=int(w["packed"]),
            value_dtype=str(w["value_dtype"]),
            k=int(w["k"]),
            backend=str(w["backend"]),
            mode=str(w["mode"]),
            cost=float(w["cost"]),
            trials=int(w["trials"]),
            source="disk",
        )
        if (
            plan.cols_per_chunk not in space["cols_per_chunk"]
            or plan.block_rows not in space["block_rows"]
            or plan.packed not in space["packed"]
            or plan.value_dtype not in space["value_dtype"]
            or plan.k != int(k)
            or plan.backend != backend
            or plan.mode != mode
            or not np.isfinite(plan.cost)
            or plan.trials < 0
        ):
            raise ValueError("winner body mismatch")
    except Exception:
        _bump("disk_rejects")
        schedule_store.quarantine(
            path, on_quarantine=lambda: _bump("quarantined")
        )
        # The caller re-searches and re-saves on a None return, which is the
        # recovery for an injected read corruption.
        faults.note_recovered("store_read")
        return None
    _bump("disk_hits")
    return plan


def _model_search(
    sell: SELLMatrix,
    candidates: List[Dict[str, int]],
    *,
    k: int,
    hw: HWConfig,
) -> Tuple[Dict[str, int], float, int]:
    """Score every candidate with the matmat cycle model on its own plan
    geometry. The width-padded stream is shared across candidates with
    the same cols_per_chunk (padding is the only cpc-dependent part)."""
    from .spmv import _sell_padded  # local: spmv routes via engine

    ci, va, _ = _sell_padded(sell)
    H = sell.slice_height
    streams: Dict[int, np.ndarray] = {}
    best: Optional[Tuple[float, Dict[str, int]]] = None
    trials = 0
    for cand in candidates:
        cpc = cand["cols_per_chunk"]
        if cpc not in streams:
            ci_p, _, _ = pad_width(ci, va, multiple=cpc)
            streams[cpc] = np.ascontiguousarray(ci_p.reshape(-1))
        cost = plan_matmat_cycles(
            streams[cpc],
            n_rows=sell.n_rows,
            n_slices=sell.n_slices,
            k=k,
            # One column per pass through the BlockSpec pipeline (depth 1),
            # as the engine's matmat runs the per-warp grid.
            k_tile=1,
            window=cpc * H,
            block_rows=cand["block_rows"],
            hw=hw,
            meta_bytes_per_elem=(
                META_BYTES_PACKED if cand["packed"] else META_BYTES_UNPACKED
            ),
            buffer_depth=1,
            value_bytes_per_elem=value_bytes_per_elem(
                cand["value_dtype"], hw=hw
            ),
        )
        trials += 1
        if best is None or cost < best[0]:
            best = (cost, cand)
    assert best is not None
    return best[1], best[0], trials


def _measure_search(
    sell: SELLMatrix,
    candidates: List[Dict[str, int]],
    *,
    k: int,
    backend: str,
    rounds: int,
) -> Tuple[Dict[str, int], float, int]:
    """Time real matmat calls per candidate, interleaved round-robin so
    machine drift hits every candidate alike. Engines come from `get_engine`
    (with the schedule store wired through), so the winner is left warm for
    the serving path that follows."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    X = jnp.asarray(
        rng.standard_normal((sell.n_cols, k)).astype(np.float32)
    )
    engines: List[SpMVEngine] = []
    for cand in candidates:
        engines.append(get_engine(
            sell,
            backend=backend,
            cols_per_chunk=cand["cols_per_chunk"],
            block_rows=cand["block_rows"],
            packed=bool(cand["packed"]),
            value_dtype=resolve_value_dtype(cand["value_dtype"]),
        ))
    for eng in engines:  # compile + first-touch outside the timed rounds
        jax.block_until_ready(eng.matmat(X))
    best_us = [float("inf")] * len(candidates)
    trials = 0
    for _ in range(rounds):
        for i, eng in enumerate(engines):
            t0 = time.perf_counter()
            jax.block_until_ready(eng.matmat(X))
            best_us[i] = min(
                best_us[i], (time.perf_counter() - t0) * 1e6
            )
            trials += 1
    i_best = int(np.argmin(best_us))
    return candidates[i_best], best_us[i_best], trials


def autotune(
    matrix: Union[CSRMatrix, SELLMatrix],
    *,
    k: int,
    backend: str = "auto",
    mode: str = "model",
    space: Optional[Dict[str, Iterable[int]]] = None,
    rounds: int = 3,
    slice_height: Optional[int] = None,
    cache_dir: Optional[str] = None,
    hw: HWConfig = DEFAULT_HW,
) -> TunedPlan:
    """Find (cols_per_chunk, block_rows, packed, value_dtype) for serving
    k-column matmats on this matrix. Returns the cached winner when one exists —
    in-memory first, then the persistent store — running zero trials; only
    a genuinely new (matrix, k, backend, mode, space) combination searches.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if mode not in TUNE_MODES:
        raise ValueError(f"mode must be one of {TUNE_MODES}, got {mode!r}")
    sell = normalize_to_sell(matrix, slice_height=slice_height, validate=False)
    resolved = resolve_backend(backend)
    norm_space = _normalize_space(space)
    digest = _sell_content_digest(sell)
    key = tune_key(
        digest, k=k, backend=resolved, mode=mode, space=norm_space, hw=hw,
        rounds=rounds if mode == "measure" else None,
    )

    with _lock:
        cached = _memory.get(key)
        if cached is not None:
            _stats["memory_hits"] += 1
    if cached is not None:
        return dataclasses.replace(cached, trials=0, source="memory")

    cache_dir = resolve_tune_cache_dir(cache_dir)
    path = tune_path(cache_dir, key) if cache_dir else None
    if path is not None and os.path.exists(path):
        plan = _load(
            path, matrix_digest=digest, key=key, space=norm_space, k=k,
            backend=resolved, mode=mode,
        )
        if plan is not None:
            with _lock:
                _memory[key] = plan
            return dataclasses.replace(plan, trials=0)

    candidates = _candidates(norm_space)
    if mode == "model":
        winner, cost, trials = _model_search(
            sell, candidates, k=k, hw=hw
        )
    else:
        winner, cost, trials = _measure_search(
            sell, candidates, k=k, backend=backend, rounds=rounds,
        )
    plan = TunedPlan(
        cols_per_chunk=winner["cols_per_chunk"],
        block_rows=winner["block_rows"],
        packed=winner["packed"],
        value_dtype=winner["value_dtype"],
        k=int(k),
        backend=resolved,
        mode=mode,
        cost=float(cost),
        trials=trials,
        source="search",
    )
    _bump("searched")
    _bump("trials", trials)
    with _lock:
        _memory[key] = plan
    if path is not None:
        _save(path, plan, matrix_digest=digest, key=key)
    return plan


def get_tuned_engine(
    matrix: Union[CSRMatrix, SELLMatrix],
    *,
    k: int,
    backend: str = "auto",
    mode: str = "model",
    space: Optional[Dict[str, Iterable[int]]] = None,
    rounds: int = 3,
    slice_height: Optional[int] = None,
    tune_cache_dir: Optional[str] = None,
    cache_dir: Optional[str] = None,
) -> Tuple[SpMVEngine, TunedPlan]:
    """Autotune, then feed the winning knobs straight into `get_engine`.
    Returns ``(engine, tuned_plan)`` — the engine is the cached one for the
    winning key, so repeat callers land on warm compiled paths. `cache_dir`
    is the *schedule* store (forwarded to the engine); `tune_cache_dir` the
    tuner's own store (both default to their env vars, the tuner falling
    back to the schedule directory)."""
    plan = autotune(
        matrix, k=k, backend=backend, mode=mode, space=space, rounds=rounds,
        slice_height=slice_height, cache_dir=tune_cache_dir,
    )
    engine = get_engine(
        matrix,
        backend=backend,
        cols_per_chunk=plan.cols_per_chunk,
        block_rows=plan.block_rows,
        packed=bool(plan.packed),
        value_dtype=resolve_value_dtype(plan.value_dtype),
        slice_height=slice_height,
        cache_dir=cache_dir,
    )
    return engine, plan
