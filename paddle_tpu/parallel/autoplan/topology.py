"""Hardware topology descriptions for the auto-parallelism planner.

A :class:`Topology` is the planner's entire view of the machine: chip
count, per-chip HBM and peak flops, and the two link classes that price
collectives — intra-slice ICI and inter-slice/host DCN (the hierarchical
topology of arxiv 2110.10548: placement cost depends on which links a
collective crosses, not just payload bytes).

Built-ins cover the CPU host (``cpuN`` — the tier-1/dev environment,
matching conftest's forced virtual devices) and common TPU slice shapes
(``v5e-8``, ``2xv5e-8`` for two slices, ...). When running live,
:func:`detect` derives a Topology from ``jax.devices()`` instead.

Numbers are *planning estimates* (peak specs, not measured), good for
ranking candidate meshes; they are not a performance model of record.
Stdlib-only at import — jax is pulled in lazily by :func:`detect`.
"""

import dataclasses
import re

GIB = 1024 ** 3


@dataclasses.dataclass(frozen=True)
class Topology:
    """One machine the planner can place a mesh on."""
    name: str
    num_chips: int            # total chips (all slices)
    hbm_bytes: int            # per-chip accelerator memory
    peak_flops: float         # per-chip peak (bf16 matmul units)
    intra_bw: float           # bytes/s per chip over in-slice links (ICI)
    inter_bw: float           # bytes/s per chip across slices/hosts (DCN)
    cores_per_chip: int = 1
    num_slices: int = 1
    hbm_bw: float = 0.0       # bytes/s per chip HBM (0 = unknown; the
    #                           roofline term serving decode is bound by
    #                           — speculation break-even depends on it)

    @property
    def chips_per_slice(self):
        return max(1, self.num_chips // max(1, self.num_slices))

    def axis_bandwidth(self, crosses_slices):
        """Per-chip bandwidth a collective sees on this axis."""
        return self.inter_bw if crosses_slices else self.intra_bw

    def to_json(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d):
        return cls(**d)


# THE chip table — per-chip characteristics by chip name: (hbm, peak bf16
# flops, ici bytes/s per chip, dcn bytes/s per chip, hbm bytes/s per
# chip). TPU peaks and HBM figures are the published ones (Google Cloud
# TPU documentation, the "TPU v4" / "v5e" / "v5p" / "v6e" system
# architecture pages); observability/perf.py reads its MFU denominator
# from here, so there is one table. The "cpu" row is a planning guess for
# the virtual-device dev host (it ranks meshes in tests; it is never a
# utilization denominator — the CPU has no peak). Link numbers are
# spec-sheet order of magnitude, enough to rank dp-over-DCN vs
# tp-over-ICI correctly; HBM bandwidth is the roofline term batch-1
# decode (and so the speculation break-even) is bound by.
_CHIPS = {
    "cpu": (4 * GIB, 5.0e10, 2.0e10, 2.0e10, 3.0e10),
    "v4": (32 * GIB, 275e12, 2.4e11, 2.5e10, 1.2e12),
    "v5e": (16 * GIB, 197e12, 1.0e11, 2.5e10, 8.2e11),
    "v5p": (95 * GIB, 459e12, 4.8e11, 2.5e10, 2.77e12),
    "v6e": (32 * GIB, 918e12, 1.8e11, 2.5e10, 1.64e12),
}

# ``jax.devices()[0].device_kind`` (lower-cased) -> chip name. On a v5e
# it is "TPU v5 lite", not anything containing "v5e"; three modules used
# to guess at it separately and each guessed differently.
_TPU_KINDS = {
    "tpu v4": "v4",
    "tpu v5 lite": "v5e",
    "tpu v5e": "v5e",
    "tpu v5p": "v5p",
    "tpu v5": "v5p",
    "tpu v6 lite": "v6e",
    "tpu v6e": "v6e",
}


def chip_name(device):
    """The one map from what JAX reports to the names the tree uses
    (observability/perf.py, :func:`detect`, ops/pallas/autotune.py all
    ask here): ``"cpu"`` for a CPU device, the chip name (``"v5e"``,
    ...) for a TPU kind the table knows. Anything else raises — a number
    priced against the wrong chip's peak is worse than no number."""
    platform = str(device.platform).lower()
    kind = str(getattr(device, "device_kind", "") or "")
    if platform == "cpu":
        return "cpu"
    name = _TPU_KINDS.get(kind.strip().lower()) if platform == "tpu" else None
    if name is None:
        raise ValueError(
            f"unknown device platform={platform!r} device_kind={kind!r}: "
            "add it (and its published peak) to "
            "paddle_tpu/parallel/autoplan/topology.py "
            f"(known TPU kinds: {sorted(_TPU_KINDS)})")
    return name


def peak_bf16_flops(device):
    """Published bf16 peak of ``device``'s chip in FLOP/s, or None for
    the CPU (no peak: ``mfu`` is null there)."""
    name = chip_name(device)
    return None if name == "cpu" else _CHIPS[name][1]

# "kind-N" (one slice of N chips) or "MxKIND-N" (M slices). cpuN means N
# virtual host devices (XLA_FLAGS --xla_force_host_platform_device_count).
_NAME_RE = re.compile(r"(?:(\d+)x)?([a-z0-9]+?)-?(\d+)$")

# presets listed by the CLI; any "(Mx)kind-N" spelling parses too
PRESETS = ("cpu1", "cpu4", "cpu8", "v5e-4", "v5e-8", "v5e-16", "v5e-64",
           "2xv5e-16", "v4-8", "v4-32", "v5p-8", "v5p-16", "v6e-8",
           "v6e-16")


def get_topology(name=None, devices=None):
    """Resolve a Topology: explicit name, else the ``autoplan_topology``
    flag, else auto-detection from the live jax devices."""
    if name is None:
        from paddle_tpu.core.flags import get_flag
        name = get_flag("autoplan_topology")
    if not name or name == "auto":
        return detect(devices)
    m = _NAME_RE.match(name.strip().lower())
    if not m or m.group(2) not in _CHIPS:
        raise KeyError(
            f"unknown topology {name!r} (want e.g. {', '.join(PRESETS)}, "
            "or 'auto' to detect from jax.devices())")
    slices = int(m.group(1)) if m.group(1) else 1
    kind, per_slice = m.group(2), int(m.group(3))
    hbm, peak, ici, dcn, mem_bw = _CHIPS[kind]
    return Topology(name=name, num_chips=slices * per_slice,
                    hbm_bytes=hbm, peak_flops=peak, intra_bw=ici,
                    inter_bw=dcn, num_slices=slices, hbm_bw=mem_bw)


def detect(devices=None):
    """Derive a Topology from the live ``jax.devices()`` (or the devices
    handed in, e.g. a described topology's): the chip's table row times
    the device count. A TPU kind :func:`chip_name` does not know
    raises."""
    if devices is None:
        import jax
        devices = jax.devices()
    devices = list(devices)
    key = chip_name(devices[0])
    hbm, peak, ici, dcn, mem_bw = _CHIPS[key]
    slices = {getattr(d, "slice_index", 0) or 0 for d in devices}
    return Topology(name=f"detected:{key}{len(devices)}",
                    num_chips=len(devices), hbm_bytes=hbm, peak_flops=peak,
                    intra_bw=ici, inter_bw=dcn,
                    num_slices=max(1, len(slices)), hbm_bw=mem_bw)
