"""Tile-shape autotuner CLI over the shared Pallas cache.

Sweeps the live kernels (flash attention, xent stats, layer norm, fused
MLP) through `ops/pallas/autotune.py` at a requested shape, then prints
the ranked tile table per entry — every candidate the sweep timed, best
first, winner marked '*'. The winners land in the JSON cache the flagged
runtime (`autotune=1`) and the autoplan cost model both read, so a sweep
here prices every later `predict()` on this chip with measured rates.

Usage:
  python tools/autotune.py sweep [--kernel all|...] [--json]
  python tools/autotune.py sweep --interpret   # CPU plumbing self-check
  python tools/autotune.py inspect [--json]    # dump the cache, ranked
  python tools/autotune.py clear               # drop the cache file

Timings need a TPU; --interpret runs the same plumbing on CPU (timings
meaningless, cache still exercised).
"""

import argparse
import json
import os
import sys

KERNELS = ("flash_attention", "xent_stats", "layer_norm", "mlp")


def _rows(entries):
    """Human table: one block per cache entry, its sweep ranked."""
    for key, rec in sorted(entries.items()):
        print(f"\n{key}")
        swept = rec.get("swept") or []
        if not swept:
            print(f"  (no sweep recorded; blocks={rec.get('blocks')})")
            continue
        best = rec.get("blocks")
        for cand in swept:
            mark = "*" if cand.get("blocks") == best else " "
            t = cand.get("time_s")
            ts = f"{t * 1e3:9.3f} ms" if t is not None else "   failed"
            bl = " ".join(f"{k}={v}" for k, v in
                          sorted(cand.get("blocks", {}).items()))
            print(f"  {mark} {ts}  {bl}")
        if rec.get("flops") and swept[0].get("time_s"):
            rate = rec["flops"] / swept[0]["time_s"]
            print(f"  achieved {rate / 1e9:.2f} GFLOP/s at the winner "
                  f"(feeds the autoplan cost model)")


def _sweep(args):
    import numpy as np
    import jax.numpy as jnp

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.ops.pallas import autotune, on_tpu

    flags = {"autotune": True}
    if args.cache:
        flags["autotune_cache"] = args.cache
    if args.interpret:
        flags["pallas_interpret"] = True
    elif not on_tpu():
        print("NOT A TPU — pass --interpret for the CPU plumbing check")
        sys.exit(2)
    set_flags(flags)

    tiny = args.interpret
    dtype = jnp.float32 if tiny else jnp.bfloat16
    b = args.batch or (1 if tiny else 8)
    h = args.heads or (2 if tiny else 12)
    t = args.seq or (128 if tiny else 512)
    d = args.hd or 64
    rows = args.rows or (64 if tiny else 4096)
    hidden = args.hidden or (128 if tiny else 768)
    vocab = args.vocab or (512 if tiny else 8192)
    inter = args.inter or 4 * hidden
    rng = np.random.RandomState(0)

    def _arr(*shape):
        return jnp.asarray(0.02 * rng.randn(*shape), dtype)

    kernels = KERNELS if args.kernel == "all" else (args.kernel,)
    before = set(autotune.cache().load().entries)
    for kernel in kernels:
        print(f"sweeping {kernel} ...", flush=True)
        if kernel == "flash_attention":
            from paddle_tpu.ops.pallas.flash_attention import flash_attention
            q = _arr(b, h, t, d)
            flash_attention(q, q, q, causal=args.causal).block_until_ready()
        elif kernel == "xent_stats":
            from paddle_tpu.ops.pallas.xent import xent_stats
            lbl = jnp.asarray(rng.randint(0, vocab, size=rows), jnp.int32)
            out = xent_stats(_arr(rows, hidden), _arr(vocab, hidden),
                             _arr(vocab), lbl)
            assert out is not None, "xent kernel refused (flag off?)"
            out[0].block_until_ready()
        elif kernel == "layer_norm":
            from paddle_tpu.ops.pallas.layer_norm import layer_norm_fused
            layer_norm_fused(_arr(rows, hidden), _arr(hidden),
                             _arr(hidden)).block_until_ready()
        else:
            from paddle_tpu.ops.pallas.mlp import fused_mlp
            fused_mlp(_arr(rows, hidden), _arr(hidden, inter), _arr(inter),
                      _arr(inter, hidden), _arr(hidden)).block_until_ready()

    entries = autotune.cache().load().entries
    touched = {k: v for k, v in entries.items()
               if v.get("kernel") in kernels}
    if args.json:
        print(json.dumps({"chip": autotune.chip_key(),
                          "new": sorted(set(touched) - before),
                          "entries": touched}, indent=2, sort_keys=True))
        return
    _rows(touched)
    cached = [k for k in touched if k in before]
    if cached:
        print(f"\n{len(cached)} entr{'y' if len(cached) == 1 else 'ies'} "
              f"served from cache (no re-sweep); `clear` to force")
    print(f"\ncache: {autotune.cache().path}")


def _inspect(args):
    from paddle_tpu.ops.pallas import autotune
    cache = autotune.cache(args.cache)
    entries = cache.load().entries
    if args.json:
        print(json.dumps({"path": cache.path, "entries": entries},
                         indent=2, sort_keys=True))
        return
    if not entries:
        print(f"cache empty: {cache.path}")
        return
    _rows(entries)
    rates = autotune.measured_rates(args.cache)
    for chip, rs in sorted(rates.items()):
        n = len(rs)
        hm = n / sum(1.0 / r for r in rs)
        print(f"\n{chip}: harmonic-mean achieved rate {hm / 1e9:.2f} "
              f"GFLOP/s over {n} entr{'y' if n == 1 else 'ies'} "
              f"(autoplan cost-model feed)")


def _clear(args):
    from paddle_tpu.ops.pallas import autotune
    cache = autotune.cache(args.cache)
    n = len(cache.load().entries)
    cache.clear()
    print(f"cleared {n} entr{'y' if n == 1 else 'ies'}: {cache.path}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sw = sub.add_parser("sweep", help="sweep kernels at a shape, print "
                                      "the ranked tile table")
    sw.add_argument("--kernel", default="all",
                    choices=("all",) + KERNELS)
    sw.add_argument("--json", action="store_true")
    sw.add_argument("--interpret", action="store_true",
                    help="CPU plumbing self-check (timings meaningless)")
    sw.add_argument("--causal", action="store_true",
                    help="causal flash variant (separate cache signature)")
    sw.add_argument("--batch", type=int, default=None)
    sw.add_argument("--heads", type=int, default=None)
    sw.add_argument("--seq", type=int, default=None)
    sw.add_argument("--hd", type=int, default=None,
                    help="attention head dim (multiple of 64)")
    sw.add_argument("--rows", type=int, default=None,
                    help="token rows for xent/layer_norm/mlp")
    sw.add_argument("--hidden", type=int, default=None)
    sw.add_argument("--vocab", type=int, default=None)
    sw.add_argument("--inter", type=int, default=None,
                    help="MLP intermediate width (default 4*hidden)")
    sw.add_argument("--cache", default=None,
                    help="cache file (default: the autotune_cache flag)")
    sw.set_defaults(fn=_sweep)
    for name, fn in (("inspect", _inspect), ("clear", _clear)):
        p = sub.add_parser(name)
        p.add_argument("--json", action="store_true")
        p.add_argument("--cache", default=None)
        p.set_defaults(fn=fn)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args.fn(args)


if __name__ == "__main__":
    main()
