"""Block-shape autotuner for the Pallas kernels.

The right VMEM tiling for a kernel depends on the operand shapes, the dtype
(narrower formats fit bigger tiles — the FPnew resource argument, §III.B),
and the backend.  Hardcoded defaults leave performance on the table, so this
module times candidate block shapes on the live backend and memoizes the
winner in a JSON cache keyed by (op, shape, dtype, backend):

  * ``best_block(op, shape, dtype)`` — the default block picker used by
    kernels/ops.py: returns the memoized winner if one exists, else the
    static heuristic (so the cold path costs one dict lookup, never a
    timing run).
  * ``autotune_matmul / autotune_attention / autotune_decode`` — run the
    actual sweep for one shape and persist the winner.
  * CLI: ``python -m repro.kernels.autotune --op matmul --shape 512x1024x512``

The cache file lives at ``$REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/repro/autotune.json``); entries from different backends never
collide, so a cache warmed on TPU is inert on CPU and vice versa.  The
repo additionally SHIPS a pre-warmed cache (``kernels/pretuned.json``,
``$REPRO_PRETUNED_CACHE`` to override) holding swept winners for the
shipped arch configs' common shapes — loaded AFTER the user cache, so a
locally-tuned winner always beats the shipped one, and only for entries
whose recorded jax version matches the running install (a stale shipped
entry silently falls back to the heuristic, same as any other version
mismatch).  Entries
are additionally keyed by the jax version that timed them — a jax upgrade
changes Mosaic/XLA codegen, so pre-upgrade winners silently invalidate and
``best_block`` falls back to the heuristic until re-tuned.  Legacy
(pre-versioning) cache files load fine: their entries are adopted once
under the running jax version (they were timed on the install that wrote
them) and re-persisted in the keyed form on the next ``record``.

Ragged workloads: batch/sequence-length dimensions are canonicalized to
power-of-two buckets in the cache key (``_bucket_shape``) — the feature
dims (head dim, matmul K/N) that are architecturally fixed stay exact.
Without bucketing, a ragged serving mix would mint one JSON entry per
distinct prompt-length combination; with it, every length in (64, 128]
shares one winner, which kernels/ops.py clamps to the live shape anyway.
Bucketed keys carry the ``v2|`` version prefix (a key-format bump): v1
entries (exact shapes) migrate on load by re-bucketing — first entry per
bucket wins — so existing caches keep resolving.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "best_block", "lookup", "record", "candidates", "default_block",
    "autotune_matmul", "autotune_attention", "autotune_decode",
    "pretuned_path",
]

_MEM: Dict[str, List[int]] = {}     # in-process cache (file mirror + new wins)
_FILE_LOADED = False


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


#: per-op axes whose sizes vary with batch/prompt length (bucketed in keys);
#: the remaining axes are architectural constants and stay exact.
_BUCKET_AXES = {"matmul": (0,), "attn": (0, 1), "decode_attn": (1,)}


def _pow2_bucket(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length() if n > 0 else 0


def _bucket_shape(op: str, shape: Sequence[int]) -> Tuple[int, ...]:
    """Canonicalize length-like dims to their next power of two so ragged
    workloads (one shape per prompt-length mix) share cache entries."""
    axes = _BUCKET_AXES.get(op, ())
    return tuple(_pow2_bucket(int(s)) if i in axes else int(s)
                 for i, s in enumerate(shape))


def _key(op: str, shape: Sequence[int], dtype, backend: Optional[str] = None
         ) -> str:
    backend = backend or jax.default_backend()
    shape = _bucket_shape(op, shape)
    return f"v2|{op}|{'x'.join(str(int(s)) for s in shape)}|" \
           f"{jnp.dtype(dtype).name}|{backend}|jax-{jax.__version__}"


def _migrate_key(k: str) -> Optional[str]:
    """Bring one on-disk key to the current (v2, bucketed) format.

    v2 keys pass through; v1 keys — 4-field pre-jax-versioning and 5-field
    jax-versioned, both with exact shapes — are re-bucketed (4-field ones
    additionally adopt the running jax version, as before).  Anything else
    is skipped, not fatal."""
    parts = k.split("|")
    if parts[0] == "v2" and len(parts) == 6:
        return k
    if len(parts) == 4:                   # op|shape|dtype|backend
        parts.append(f"jax-{jax.__version__}")
    if len(parts) != 5:
        return None
    try:
        shape = _bucket_shape(parts[0], [int(x) for x in parts[1].split("x")])
    except ValueError:
        return None
    parts[1] = "x".join(str(s) for s in shape)
    return "|".join(["v2"] + parts)


def pretuned_path() -> str:
    return os.environ.get(
        "REPRO_PRETUNED_CACHE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "pretuned.json"))


def _load_pretuned() -> None:
    """Adopt the shipped warm cache.  Called after the user's disk cache
    (``setdefault``: local winners beat shipped ones).  Only v2 entries
    whose key carries the RUNNING jax version are adopted — a pretuned
    file generated under another jax is a silent no-op (heuristic
    fallback), because codegen changed under the timed winners."""
    try:
        with open(pretuned_path()) as f:
            ship = json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(ship, dict):
        return
    tag = f"jax-{jax.__version__}"
    for k, v in ship.get("entries", {}).items():
        try:
            block = [int(x) for x in v]
        except (TypeError, ValueError):
            continue
        parts = k.split("|")
        if parts[0] != "v2" or len(parts) != 6 or parts[5] != tag:
            continue                     # stale version / malformed: skip
        _MEM.setdefault(k, block)


def _load_file() -> None:
    global _FILE_LOADED
    if _FILE_LOADED:
        return
    _FILE_LOADED = True
    path = cache_path()
    try:
        with open(path) as f:
            disk = json.load(f)
    except (OSError, ValueError):
        disk = {}
    for k, v in disk.items():
        try:
            block = [int(x) for x in v]
        except (TypeError, ValueError):
            continue                     # unknown entry shape: skip, don't die
        k = _migrate_key(k)
        if k is not None:                # first entry per bucket wins
            _MEM.setdefault(k, block)
    _load_pretuned()


def reset(clear_env_cache: bool = False) -> None:
    """Drop the in-process cache (tests; or after pointing
    $REPRO_AUTOTUNE_CACHE somewhere else)."""
    global _FILE_LOADED
    _MEM.clear()
    _FILE_LOADED = False
    if clear_env_cache:
        try:
            os.remove(cache_path())
        except OSError:
            pass


def lookup(op: str, shape: Sequence[int], dtype,
           backend: Optional[str] = None) -> Optional[Tuple[int, ...]]:
    _load_file()
    v = _MEM.get(_key(op, shape, dtype, backend))
    return tuple(v) if v is not None else None


def record(op: str, shape: Sequence[int], dtype, block: Sequence[int],
           backend: Optional[str] = None, persist: bool = True) -> None:
    _load_file()
    _MEM[_key(op, shape, dtype, backend)] = [int(x) for x in block]
    if persist:
        path = cache_path()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(_MEM, f, indent=2, sort_keys=True)


# ---------------------------------------------------------------------------
# heuristics + candidate grids
# ---------------------------------------------------------------------------
def _mult_128(x: int) -> int:
    return -(-int(x) // 128) * 128


def default_block(op: str, shape: Sequence[int]) -> Tuple[int, ...]:
    """The static fallbacks (what ops.py hardcoded before the autotuner)."""
    if op == "matmul":
        m, k, n = shape
        return (min(128, max(8, m)), max(128, min(512, k)),
                max(128, min(128, n)))
    if op == "attn":                 # (sq, skv, d) -> (bq, bk)
        sq, skv, _ = shape
        return (min(128, max(8, sq)), min(128, max(128, skv)))
    if op == "decode_attn":          # (g, smax, d) -> (bk,)
        _, smax, _ = shape
        return (min(512, _mult_128(max(smax, 1))),)
    raise ValueError(op)


def candidates(op: str, shape: Sequence[int]) -> List[Tuple[int, ...]]:
    """Legal candidate tilings for one op/shape (deduped, heuristic first so
    ties keep the old default)."""
    out = [default_block(op, shape)]
    if op == "matmul":
        m, k, n = shape
        for bm in (32, 64, 128, 256):
            for bk in (128, 256, 512):
                for bn in (128, 256):
                    c = (min(bm, max(8, m)), max(128, min(bk, _mult_128(k))),
                         max(128, min(bn, _mult_128(n))))
                    if c not in out:
                        out.append(c)
    elif op == "attn":
        sq, skv, _ = shape
        for bq in (32, 64, 128, 256):
            for bk in (128, 256, 512):
                c = (min(bq, max(8, sq)), max(128, min(bk, _mult_128(skv))))
                if c not in out:
                    out.append(c)
    elif op == "decode_attn":
        _, smax, _ = shape
        for bk in (128, 256, 512, 1024):
            c = (max(128, min(bk, _mult_128(max(smax, 1)))),)
            if c not in out:
                out.append(c)
    else:
        raise ValueError(op)
    return out


def best_block(op: str, shape: Sequence[int], dtype,
               backend: Optional[str] = None) -> Tuple[int, ...]:
    """Default block picker for kernels/ops.py: memoized winner, else the
    static heuristic.  Never times anything."""
    return lookup(op, shape, dtype, backend) or default_block(op, shape)


# ---------------------------------------------------------------------------
# timing sweeps
# ---------------------------------------------------------------------------
def _time_one(fn: Callable[[], jax.Array], repeats: int = 3) -> float:
    jax.block_until_ready(fn())            # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep(op: str, shape: Sequence[int], dtype, make_fn, *,
           repeats: int = 3, persist: bool = True, verbose: bool = False
           ) -> Tuple[Tuple[int, ...], Dict[Tuple[int, ...], float]]:
    timings: Dict[Tuple[int, ...], float] = {}
    for block in candidates(op, shape):
        try:
            timings[block] = _time_one(make_fn(block), repeats)
        except Exception as e:           # illegal tiling for this backend
            if verbose:
                print(f"  {op} {block}: skipped ({type(e).__name__})")
            continue
        if verbose:
            print(f"  {op} {block}: {timings[block] * 1e3:.3f} ms")
    assert timings, f"no legal candidate for {op} {shape}"
    winner = min(timings, key=timings.get)
    record(op, shape, dtype, winner, persist=persist)
    return winner, timings


def autotune_matmul(m: int, k: int, n: int, dtype=jnp.float32, *,
                    interpret: Optional[bool] = None, repeats: int = 3,
                    persist: bool = True, verbose: bool = False):
    # interpret=None resolves from the platform in the kernel wrappers:
    # winners are keyed by backend, so a sweep times what it will run
    from . import ops as kops
    a = jax.random.normal(jax.random.key(0), (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.key(1), (k, n), jnp.float32).astype(dtype)
    mk = lambda blk: functools.partial(kops.tp_matmul, a, b, block=blk,
                                       interpret=interpret)
    return _sweep("matmul", (m, k, n), dtype, mk, repeats=repeats,
                  persist=persist, verbose=verbose)


def autotune_attention(sq: int, skv: int, d: int, heads: int = 4,
                       dtype=jnp.float32, *, interpret: Optional[bool] = None,
                       repeats: int = 3, persist: bool = True,
                       verbose: bool = False):
    from . import ops as kops
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, heads, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, heads, skv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, heads, skv, d), jnp.float32).astype(dtype)
    mk = lambda blk: functools.partial(kops.flash_attention, q, k, v,
                                       bq=blk[0], bk=blk[1],
                                       interpret=interpret)
    return _sweep("attn", (sq, skv, d), dtype, mk, repeats=repeats,
                  persist=persist, verbose=verbose)


def autotune_decode(group: int, smax: int, d: int, heads: int = 4,
                    dtype=jnp.float32, *, interpret: Optional[bool] = None,
                    repeats: int = 3, persist: bool = True,
                    verbose: bool = False):
    from . import ops as kops
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, heads * group, 1, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, heads, smax, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, heads, smax, d), jnp.float32)
    g_pad = max(8, group)
    mk = lambda blk: functools.partial(
        kops.decode_attention, q.astype(dtype), k.astype(dtype),
        v.astype(dtype), kv_len=smax, bk=blk[0], interpret=interpret)
    return _sweep("decode_attn", (g_pad, smax, d), dtype, mk,
                  repeats=repeats, persist=persist, verbose=verbose)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--op", choices=("matmul", "attn", "decode_attn"),
                    required=True)
    ap.add_argument("--shape", required=True,
                    help="matmul: MxKxN; attn: SQxSKVxD; decode_attn: GxSMAXxD")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    try:
        dims = tuple(int(x) for x in args.shape.lower().split("x"))
    except ValueError:
        ap.error(f"--shape wants AxBxC integers, got {args.shape!r}")
    if len(dims) != 3:
        ap.error(f"--shape wants exactly 3 'x'-separated dims, "
                 f"got {args.shape!r}")
    dtype = jnp.dtype(args.dtype)
    fn = {"matmul": autotune_matmul, "attn": autotune_attention,
          "decode_attn": autotune_decode}[args.op]
    winner, timings = fn(*dims, dtype=dtype, repeats=args.repeats,
                         verbose=True)
    print(f"winner for {args.op} {args.shape} [{dtype}] on "
          f"{jax.default_backend()}: {winner} "
          f"({timings[winner] * 1e3:.3f} ms) -> {cache_path()}")


if __name__ == "__main__":
    main()
