"""Hardware-peak and cost-analysis helpers for the Trainer's step
telemetry.

MFU arithmetic has ONE home: the per-step RunLog records, the autoplan
calibration and tools/run_report.py all compute achieved/peak from the
same table
(autoplan/topology.py's chip table, keyed by the chip JAX reports). jax
is imported lazily.
"""


def peak_flops():
    """Published per-chip peak bf16 FLOP/s of device 0, or None on the
    CPU (which has no peak). A TPU kind the chip table does not know
    raises rather than borrowing another chip's number."""
    import jax
    from paddle_tpu.parallel.autoplan.topology import peak_bf16_flops
    return peak_bf16_flops(jax.devices()[0])


def cost_flops(jitted, *args):
    """FLOPs per call from XLA cost analysis; 0.0 where the backend has
    none to give. A step that fails to lower or compile raises — it
    would fail the same way when run."""
    c = jitted.lower(*args).compile().cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else None
    return float(c.get("flops", 0.0)) if c else 0.0


def mfu(flops_per_step, step_s):
    """Achieved fraction of the chip's peak for one step, or None (no
    flop count, no time, or a device without a peak — the CPU)."""
    if not flops_per_step or not step_s or step_s <= 0:
        return None
    peak = peak_flops()
    return flops_per_step / step_s / peak if peak else None


# memory_stats keys worth carrying in a step record (full dict is noisy)
_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_alloc_size")


def device_memory_stats(device=None):
    """Compact HBM stats for one device ({'peak_bytes_in_use': ...}), or
    None where the backend has no allocator stats (CPU)."""
    try:
        import jax
        d = device if device is not None else jax.local_devices()[0]
        ms = d.memory_stats()
    except Exception:
        return None
    if not ms:
        return None
    out = {k: int(ms[k]) for k in _MEM_KEYS if k in ms}
    return out or {k: int(v) for k, v in ms.items()
                   if isinstance(v, (int, float))} or None
