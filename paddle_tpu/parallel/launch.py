"""Multi-host launcher + distributed runtime init.

Ref: /root/reference/python/paddle/distributed/launch.py (multi-proc-per-node
launcher exporting PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS, :78-81,159) and the gen_nccl_id gRPC bootstrap
(operators/distributed_ops/gen_nccl_id_op.cc).

TPU-first: `jax.distributed.initialize` + the JAX coordination service
replace both — one call wires every host into the global mesh over DCN; no
id broadcast, no per-trainer endpoint lists. The CLI here mirrors the
reference's `python -m paddle.distributed.launch` surface for multi-process
CPU/GPU simulation and multi-host TPU pods.

Usage:
  python -m paddle_tpu.parallel.launch --nproc 4 train.py  (local sim)
  # on TPU pods the platform sets the env; just call init_distributed().
"""

import argparse
import os
import re
import subprocess
import sys

import jax

from paddle_tpu.core import flags


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Initialize the multi-host runtime (replaces gen_nccl_id bootstrap).
    No-ops on single-process."""
    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("PT_COORDINATOR")
    if coordinator_address is None:
        return False  # single process
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes or env.get("PT_NUM_PROCESSES", 1)),
        process_id=int(process_id or env.get("PT_PROCESS_ID", 0)))
    return True


def _gather_retryable(exc):
    """host_allgather's wait-for-peer predicate: an absent file is the
    normal not-published-yet state here (unlike remote I/O, where
    core/retry.py treats FileNotFoundError as an answer), and
    ValueError/EOFError are a peer's np.save caught mid-os.replace."""
    return isinstance(exc, (FileNotFoundError, ValueError, EOFError,
                            OSError))


def host_allgather(arr, rank, world, exchange_dir, tag, timeout=60.0,
                   generation=None, policy=None, ragged=False):
    """All-gather host numpy arrays across local processes via the shared
    filesystem — no XLA collectives, so it works on backends where
    multi-process computations are unimplemented (jax 0.4.x CPU, where
    multihost_utils.process_allgather raises inside the worker). Each
    rank atomically publishes its array (temp file + os.replace), then
    waits for the others under a core/retry.py RetryPolicy (jittered
    backoff, overall deadline = `timeout`; pass `policy` to override).
    `tag` must be unique per collective call site. Returns
    [world, *arr.shape], or a list of `world` per-rank arrays when
    `ragged=True` (for message-style exchanges — e.g. the fleet
    router's JSON command/response wire — where ranks legitimately
    publish different-length payloads that np.stack would reject).

    `generation` isolates incarnations of the SAME tag (the fleet
    router's respawned subprocess replicas restart their command
    sequence at 0): files are published as `{tag}.g{generation}_{rank}`
    and any file of this tag from an older generation is removed before
    publishing, so a respawned rank can never read a dead peer's stale
    payload as fresh."""
    import numpy as np

    from paddle_tpu.core.retry import RetryPolicy

    os.makedirs(exchange_dir, exist_ok=True)
    arr = np.asarray(arr)
    base = tag if generation is None else f"{tag}.g{int(generation)}"
    if generation is not None:
        stale = re.compile(rf"^{re.escape(tag)}\.g(\d+)_\d+\.npy$")
        for name in os.listdir(exchange_dir):
            m = stale.match(name)
            if m and int(m.group(1)) < int(generation):
                try:
                    os.remove(os.path.join(exchange_dir, name))
                except OSError:
                    pass           # the other rank's cleanup won the race
    tmp = os.path.join(exchange_dir, f".{base}_{rank}.tmp.npy")
    with open(tmp, "wb") as f:
        np.save(f, arr)
    os.replace(tmp, os.path.join(exchange_dir, f"{base}_{rank}.npy"))
    pol = policy or RetryPolicy(
        max_attempts=1_000_000_000, backoff_base_s=0.005,
        backoff_max_s=0.05, backoff_multiplier=1.5, deadline_s=timeout,
        retryable=_gather_retryable)
    out = []
    for r in range(world):
        path = os.path.join(exchange_dir, f"{base}_{r}.npy")

        def load_peer(p=path):
            return np.load(p)

        try:
            out.append(pol.call(load_peer))
        except Exception as e:
            if not _gather_retryable(e):
                raise
            raise TimeoutError(
                f"host_allgather({tag}): rank {r} did not publish "
                f"within {timeout}s") from e
    return out if ragged else np.stack(out)


def launch_local(nproc, script, script_args=(), base_port=12355,
                 env_extra=None):
    """Spawn nproc local processes wired into one JAX distributed job
    (ref: launch.py _start_procs). Used by multi-host simulation tests.

    CPU-only today: the children run on the CPU unless the caller's
    environment names another platform in ``JAX_PLATFORMS`` — several
    local processes cannot share one chip, and a parent that has touched
    JAX holds it."""
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "PT_COORDINATOR": f"127.0.0.1:{base_port}",
            "PT_NUM_PROCESSES": str(nproc),
            "PT_PROCESS_ID": str(rank),
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
        })
        env.update(env_extra or {})
        procs.append(subprocess.Popen(
            [sys.executable, script, *script_args], env=env))
    return procs


def wait_all(procs, timeout=600):
    """Wait for all ranks; raise if any failed (ref: launch.py watch loop —
    terminates the job when any proc dies)."""
    codes = []
    try:
        for p in procs:
            codes.append(p.wait(timeout=timeout))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(c != 0 for c in codes):
        raise RuntimeError(f"distributed job failed, exit codes: {codes}")
    return codes


def main():
    ap = argparse.ArgumentParser(description="paddle_tpu distributed launcher")
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--port", type=int, default=12355)
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    procs = launch_local(args.nproc, args.script, args.script_args, args.port)
    wait_all(procs)


if __name__ == "__main__":
    main()
