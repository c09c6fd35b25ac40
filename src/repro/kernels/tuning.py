"""Tile-size selection for the paired / dense Pallas GEMMs.

Selection is layered, strongest signal first:

1. **Measured** — a :class:`TileCache` entry, keyed by
   ``(M, N, K, dtype, segments, pool)``.  Entries are produced by the
   :func:`autotune_blocks` search (driven by ``benchmarks/roofline.py``'s
   sweep, or any caller with a runner) and persisted to a versioned on-disk
   JSON, so a tuned machine keeps its winners across processes.  A warm
   cache hit always wins over the heuristic.
2. **Heuristic** — the VMEM-budget model below: the kernel's working set
   per program is

       xi (bm·bk) + xj (bm·bk)            [paired segment]
     + xr (bm·bk)                         [residual segment]
     + kmat / w_res (bk·bn)               [weight tile per live segment]
     + acc (bm·bn fp32) + out (bm·bn)

   all times the element size, with double-buffering on the streamed inputs
   and the activation streams / accumulator scaled ×4 when the fused 2×2
   pooling epilogue is active (window-major layout).  ``choose_blocks``
   clamps ``block_m``/``block_n`` to the actual problem dims (a LeNet conv
   GEMM of M=100, N=16 must not budget a 128×128 tile) and then picks the
   largest ``block_k`` that fits.

The heuristic is the safe fallback for any shape never measured; the
autotuner is what closes the gap the ROADMAP flagged between the static
VMEM model and real hardware behaviour.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
from pathlib import Path

# Usable VMEM budget per core: ~16 MB physical, keep headroom for the
# compiler's own buffers and semaphores.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# Lane/sublane-friendly candidates, largest first.
_BLOCK_K_CANDIDATES = (2048, 1024, 512, 256, 128)
# 2×2 fused pooling streams 4 GEMM rows per pooled output row.
_POOL_WINDOW = 4


@dataclasses.dataclass(frozen=True)
class TileConfig:
    block_m: int
    block_n: int
    block_k: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def kernel_vmem_bytes(
    bm: int,
    bn: int,
    bk: int,
    *,
    dtype_bytes: int = 2,
    has_pairs: bool = True,
    has_resid: bool = True,
    double_buffer: bool = True,
    pool_window: int = 1,
    residual: bool = False,
) -> int:
    """Estimated VMEM working set of one program of the paired kernel.

    ``pool_window > 1`` models the fused-pooling megakernel: every
    activation stream and the fp32 accumulator carry the window axis; the
    weight tiles and the (pooled) output tile do not.  ``residual`` models
    the fused skip-connection epilogue: one extra output-shaped ``(bm, bn)``
    operand streamed (double-buffered) alongside the activations.
    """
    x_streams = 0
    w_streams = 0
    if has_pairs:
        x_streams += 2 * bm * bk  # xi, xj tiles
        w_streams += bk * bn  # kmat tile
    if has_resid:
        x_streams += bm * bk  # xr tile
        w_streams += bk * bn  # w_res tile
    if residual:
        w_streams += bm * bn  # fused-residual tile (output-shaped stream)
    buf = 2 if double_buffer else 1
    streams = pool_window * x_streams + w_streams
    fixed = pool_window * bm * bn * 4 + bm * bn * dtype_bytes  # acc + out
    return buf * streams * dtype_bytes + fixed


def estimate_pallas_vmem_bytes(
    in_blocks,
    out_blocks,
    scratch_blocks=(),
    *,
    double_buffer: bool = True,
) -> int:
    """Static VMEM working set of one ``pallas_call`` program from its block specs.

    The generic counterpart of :func:`kernel_vmem_bytes` (which models the
    paired kernel's named streams): each argument is an iterable of
    ``(block_shape, dtype_bytes)``.  Streamed inputs are double-buffered,
    outputs and scratch are resident once.  Squeezed block dims (``None``,
    ``Squeezed()``) occupy one element.  This is what the static
    analysis pass charges against :data:`VMEM_BUDGET_BYTES` before anything
    runs.
    """

    def tile(shape, nbytes) -> int:
        n = 1
        for d in shape:
            # a pallas block dim is Blocked(block_size) or a bare int; None
            # and Squeezed() are one element
            size = getattr(d, "block_size", d)
            n *= size if isinstance(size, int) else 1
        return n * nbytes

    buf = 2 if double_buffer else 1
    total = sum(buf * tile(s, b) for s, b in in_blocks)
    total += sum(tile(s, b) for s, b in out_blocks)
    total += sum(tile(s, b) for s, b in scratch_blocks)
    return total


def _round_up_pow2(x: int, cap: int) -> int:
    p = 1
    while p < x and p < cap:
        p *= 2
    return min(p, cap)


# ---------------------------------------------------------------------------
# persisted tile cache (measured winners beat the heuristic)
# ---------------------------------------------------------------------------

CACHE_VERSION = 1
DEFAULT_CACHE_PATH = Path(".cache") / "tile_cache.json"


def cache_key(
    M: int,
    N: int,
    P: int,
    R: int,
    *,
    dtype: str = "",
    dtype_bytes: int = 2,
    pool: str = "none",
    blocks: int = 1,
    residual: bool = False,
) -> str:
    """Stable key for one kernel problem: (M, N, K, dtype, segments, pool).

    ``segments`` is the (P, R) split of the contraction — the same K tiles
    differently depending on how many lanes pair off, so it is part of the
    problem identity, not just K.  ``blocks > 1`` marks the column-blocked
    layout (per-n-block segment metadata; N/P/R are then the *per-block*
    lane counts) and ``residual`` the fused skip-connection epilogue (one
    extra output-shaped stream competing for VMEM) — each suffix is only
    appended when active so existing persisted caches keep their keys.
    """
    K = 2 * P + R
    dt = dtype or f"b{dtype_bytes}"
    suffix = f"-x{blocks}" if blocks > 1 else ""
    if residual:
        suffix += "-res"
    return f"M{M}-N{N}-K{K}-{dt}-p{P}r{R}-{pool}{suffix}"


class TileCache:
    """Versioned on-disk map from :func:`cache_key` to a measured TileConfig.

    The JSON layout is ``{"version": 1, "entries": {key: {"block_m": …,
    "block_n": …, "block_k": …, "time_s": …, "source": …}}}``.  A version
    mismatch (or unreadable file) loads as empty — stale schemas never
    poison tile selection.  ``put`` keeps an entry's provenance so the
    benchmark sweep can report where each winner came from.
    """

    def __init__(self, path: str | Path | None = None):
        self.path = Path(path) if path is not None else DEFAULT_CACHE_PATH
        self.entries: dict[str, dict] = {}
        self._load()

    def _load(self) -> None:
        try:
            raw = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return
        if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
            return
        entries = raw.get("entries", {})
        if isinstance(entries, dict):
            self.entries = {
                k: v
                for k, v in entries.items()
                if isinstance(v, dict)
                and all(isinstance(v.get(f), int) for f in ("block_m", "block_n", "block_k"))
            }

    def get(self, key: str) -> TileConfig | None:
        e = self.entries.get(key)
        if e is None:
            return None
        return TileConfig(e["block_m"], e["block_n"], e["block_k"])

    def put(
        self,
        key: str,
        config: TileConfig,
        *,
        time_s: float | None = None,
        source: str = "measured",
    ) -> None:
        entry: dict = dict(config.as_dict(), source=source)
        if time_s is not None:
            entry["time_s"] = time_s
        self.entries[key] = entry

    def save(self) -> Path:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.path.write_text(
            json.dumps(
                {"version": CACHE_VERSION, "entries": self.entries}, indent=2
            )
        )
        return self.path

    def __len__(self) -> int:
        return len(self.entries)


_cache_state = threading.local()


def active_tile_cache() -> TileCache | None:
    return getattr(_cache_state, "cache", None)


class use_tile_cache:
    """Context manager installing a TileCache for :func:`choose_blocks`.

    Accepts a :class:`TileCache` or a path (loaded on entry).  Thread-local,
    like the GEMM/conv policies in ``kernels.ops`` — wrap the trace of a
    step (``PerfKnobs(tile_cache=…)`` does this through ``perf_context``).
    """

    def __init__(self, cache: TileCache | str | Path):
        self.cache = cache if isinstance(cache, TileCache) else TileCache(cache)
        self._prev: TileCache | None = None

    def __enter__(self) -> TileCache:
        self._prev = active_tile_cache()
        _cache_state.cache = self.cache
        return self.cache

    def __exit__(self, *exc) -> None:
        _cache_state.cache = self._prev


# ---------------------------------------------------------------------------
# heuristic chooser (cache-aware)
# ---------------------------------------------------------------------------


def choose_blocks(
    M: int,
    N: int,
    P: int,
    R: int = 0,
    *,
    dtype_bytes: int = 2,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    dtype: str = "",
    pool: str = "none",
    use_cache: bool = True,
    blocks: int = 1,
    residual: bool = False,
) -> TileConfig:
    """Pick (block_m, block_n, block_k) for a paired GEMM of the given shape.

    ``P`` paired lanes + ``R`` residual lanes (pass ``P=0`` for a plain
    dense GEMM of contraction length ``R``); ``pool`` budgets the fused 2×2
    pooling epilogue's window-major streams.  For the column-blocked layout
    pass ``blocks=n_blocks`` with the *per-block* (N, P, R) — the lane tile
    is pinned to N there, so only block_m/block_k are really free.
    ``residual`` budgets the fused skip-connection stream.  A warm
    :class:`TileCache` entry (installed via :class:`use_tile_cache`) is
    returned in preference to the heuristic.
    """
    if use_cache:
        cache = active_tile_cache()
        if cache is not None:
            hit = cache.get(cache_key(
                M, N, P, R, dtype=dtype, dtype_bytes=dtype_bytes, pool=pool,
                blocks=blocks, residual=residual,
            ))
            if hit is not None:
                return hit

    K_eff = max(P, R, 1)
    pw = _POOL_WINDOW if pool != "none" else 1
    # clamp to the problem dims: padding a 100×16 conv GEMM out to 128×128
    # tiles would spend VMEM on dead lanes that a larger block_k can use
    bm = min(_round_up_pow2(M, 128), M)
    bn = min(_round_up_pow2(N, 128), N)
    has_pairs, has_resid = P > 0, R > 0

    for bk in _BLOCK_K_CANDIDATES:
        if bk > K_eff and bk != _BLOCK_K_CANDIDATES[-1]:
            continue
        bk_eff = min(bk, K_eff)
        if (
            kernel_vmem_bytes(
                bm, bn, bk_eff,
                dtype_bytes=dtype_bytes,
                has_pairs=has_pairs, has_resid=has_resid,
                pool_window=pw, residual=residual,
            )
            <= vmem_budget
        ):
            return TileConfig(bm, bn, min(bk, K_eff))

    # fall back to shrinking the output tile until the smallest k-tile fits
    bk = min(_BLOCK_K_CANDIDATES[-1], K_eff)
    while bm * bn > 8 * 8 and (
        kernel_vmem_bytes(
            bm, bn, bk,
            dtype_bytes=dtype_bytes,
            has_pairs=has_pairs, has_resid=has_resid,
            pool_window=pw, residual=residual,
        )
        > vmem_budget
    ):
        if bm >= bn:
            bm //= 2
        else:
            bn //= 2
    return TileConfig(max(bm, 8), max(bn, 8), bk)


def resolve_blocks(
    M: int,
    N: int,
    P: int,
    R: int,
    *,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    dtype_bytes: int = 2,
    dtype: str = "",
    pool: str = "none",
    blocks: int = 1,
    residual: bool = False,
) -> TileConfig:
    """Fill any zero block size from the cache/heuristic (explicit wins).

    ``blocks`` is the column-block count of the blocked paired GEMM —
    including the experts-as-blocks layout, where it is ``E`` (or
    ``E·ceil(F/bn)``) and scales the per-launch metadata VMEM the
    heuristic budgets for."""
    if block_m and block_n and block_k:
        return TileConfig(block_m, block_n, block_k)
    auto = choose_blocks(
        M, N, P, R, dtype_bytes=dtype_bytes, dtype=dtype, pool=pool,
        blocks=blocks, residual=residual,
    )
    return TileConfig(
        block_m or auto.block_m,
        block_n or auto.block_n,
        block_k or auto.block_k,
    )


# ---------------------------------------------------------------------------
# measured autotuning (drives the cache)
# ---------------------------------------------------------------------------


def measure(fn, *, reps: int = 3, warmup: int = 1) -> float:
    """Best-of-``reps`` wall-clock of ``fn()``, blocking on jax arrays.

    On TPU this times real kernel executions (compile cost is paid in the
    warmup runs); in this container it times interpret mode — still the
    right *mechanism*, exercised end to end, with hardware-meaningful
    numbers arriving the moment the same sweep runs on a TPU.
    """
    def _block(out):
        with contextlib.suppress(ImportError, TypeError):
            import jax

            jax.block_until_ready(out)
        return out

    for _ in range(warmup):
        _block(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def candidate_configs(
    M: int,
    N: int,
    P: int,
    R: int,
    *,
    dtype_bytes: int = 2,
    pool: str = "none",
    vmem_budget: int = VMEM_BUDGET_BYTES,
    block_ks: tuple[int, ...] = _BLOCK_K_CANDIDATES,
) -> list[TileConfig]:
    """VMEM-feasible tile candidates for one problem (heuristic pick included).

    The search space is deliberately small — clamped bm/bn plus one halved
    variant of each, crossed with the lane-friendly ``block_k`` ladder —
    because each candidate costs a measured kernel execution.
    """
    K_eff = max(P, R, 1)
    pw = _POOL_WINDOW if pool != "none" else 1
    has_pairs, has_resid = P > 0, R > 0
    bm0 = min(_round_up_pow2(M, 128), M)
    bn0 = min(_round_up_pow2(N, 128), N)
    bms = sorted({bm0, max(bm0 // 2, 8)}, reverse=True)
    bns = sorted({bn0, max(bn0 // 2, 8)}, reverse=True)
    bks = sorted({min(bk, K_eff) for bk in block_ks}, reverse=True)

    out = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                fits = kernel_vmem_bytes(
                    bm, bn, bk,
                    dtype_bytes=dtype_bytes,
                    has_pairs=has_pairs, has_resid=has_resid,
                    pool_window=pw,
                ) <= vmem_budget
                if fits:
                    out.append(TileConfig(bm, bn, bk))
    heur = choose_blocks(
        M, N, P, R, dtype_bytes=dtype_bytes, pool=pool,
        vmem_budget=vmem_budget, use_cache=False,
    )
    if heur not in out:
        out.append(heur)
    return out


def autotune_blocks(
    runner,
    M: int,
    N: int,
    P: int,
    R: int,
    *,
    dtype_bytes: int = 2,
    dtype: str = "",
    pool: str = "none",
    cache: TileCache | None = None,
    candidates: list[TileConfig] | None = None,
    reps: int = 3,
    warmup: int = 1,
) -> tuple[TileConfig, list[dict]]:
    """Measure every candidate tile config and persist the winner.

    ``runner(config)`` must execute the kernel for this problem at
    ``config`` and return its (jax) result; :func:`measure` times it.
    Returns ``(winner, records)`` where each record carries the config, its
    measured time, and its VMEM estimate (the roofline bench prints these).
    When ``cache`` is given the winner is written through and saved, so the
    next :func:`choose_blocks` on this problem takes the measured pick.
    """
    cands = candidates or candidate_configs(
        M, N, P, R, dtype_bytes=dtype_bytes, pool=pool
    )
    pw = _POOL_WINDOW if pool != "none" else 1
    records = []
    best: TileConfig | None = None
    best_t = float("inf")
    for cfg in cands:
        t = measure(lambda cfg=cfg: runner(cfg), reps=reps, warmup=warmup)
        records.append(
            {
                **cfg.as_dict(),
                "time_s": t,
                "vmem_bytes": kernel_vmem_bytes(
                    cfg.block_m, cfg.block_n,
                    min(cfg.block_k, max(P, R, 1)),
                    dtype_bytes=dtype_bytes,
                    has_pairs=P > 0, has_resid=R > 0,
                    pool_window=pw,
                ),
            }
        )
        if t < best_t:
            best, best_t = cfg, t
    assert best is not None, "no feasible tile candidates"
    if cache is not None:
        cache.put(
            cache_key(M, N, P, R, dtype=dtype, dtype_bytes=dtype_bytes, pool=pool),
            best,
            time_s=best_t,
        )
        cache.save()
    return best, records
