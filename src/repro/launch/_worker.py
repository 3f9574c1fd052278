"""Worker process entry point for the multi-process launch path.

Run as ``python -m repro.launch._worker --id K --root DIR ...`` by
``launch/distributed.py``'s :class:`Cluster` (long-lived protocol
workers) and :func:`selftest` (oneshot ``jax.distributed`` bring-up).

IMPORT DISCIPLINE: this module imports ONLY stdlib +
``launch/channel.py``.  In ``--jax distributed`` mode the worker must
call ``jax.distributed.initialize`` before any jax computation, and the
``repro.runtime`` import chain materializes device constants at import
time — so jax (and ``launch/mesh.py``) are imported lazily, AFTER
initialize.  Keep it that way.

The worker's life:

  * write a ``ready.json`` report + first heartbeat (the lease uptake);
  * loop: renew the lease every ``--hb-interval``; follow ``cmd.json``
    (shard assignment, shutdown); ack each broadcast stratum task —
    with a real on-device computation when a jax mode is on;
  * exit when orphaned (the coordinator died) or told to shut down.

A SIGKILL simply stops the loop — heartbeats cease and the coordinator's
lease table notices; a SIGSTOP freezes it — heartbeats arrive late, the
straggle signal.  Nothing here cooperates with its own failure.
"""
from __future__ import annotations

import os
import time
from typing import List

from repro.launch.channel import (ack_path, read_json, stratum_path,
                                  worker_dir, write_heartbeat, write_json)

JAX_MODES = ("off", "local", "distributed")


def _enable_cpu_gloo() -> None:
    """Cross-process CPU collectives need the gloo backend."""
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _report_distributed(args) -> dict:
    """Distributed-mode bring-up: join the jax cluster, build the global
    flat mesh, run one cross-process collective, report ownership."""
    _enable_cpu_gloo()
    import jax
    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.num_processes,
                               process_id=args.process_id)
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    from repro.launch.mesh import flat_mesh, local_shards
    mesh = flat_mesh(devices=jax.devices())
    gathered = multihost_utils.process_allgather(
        jnp.asarray([args.process_id], jnp.int32))
    return {
        "process_index": int(jax.process_index()),
        "num_processes": int(jax.process_count()),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "num_shards": int(mesh.devices.size),
        "local_shards": local_shards(mesh),
        "allgather": [int(x) for x in gathered.reshape(-1)],
    }


def _device_work(seq: int):
    """A real on-device computation per stratum ack (local/distributed
    jax modes): proves the worker's runtime is alive, not just its
    event loop."""
    import jax.numpy as jnp
    return float(jnp.sum(jnp.arange(256, dtype=jnp.float32) + seq))


def worker_main(args) -> int:
    root = args.root
    wid = args.id
    os.makedirs(worker_dir(root, wid), exist_ok=True)
    report = {"worker_id": wid, "jax": args.jax, "pid": os.getpid()}
    if args.jax == "distributed":
        report.update(_report_distributed(args))
    elif args.jax == "local":
        import jax
        report["local_devices"] = len(jax.devices())
    write_json(os.path.join(worker_dir(root, wid), "ready.json"), report)
    write_heartbeat(root, wid, 0, jax=args.jax)
    if args.oneshot:
        return 0

    ppid = os.getppid()
    shards: List[int] = []
    hb_seq, last_hb = 1, time.monotonic()
    last_ack_seq = -1
    cmd_seq = -1
    poll_s = max(min(args.hb_interval / 4.0, 0.02), 0.001)
    while True:
        now = time.monotonic()
        if os.getppid() != ppid:          # coordinator gone: orphan exit
            return 1
        try:
            cmd = read_json(os.path.join(worker_dir(root, wid),
                                         "cmd.json"))
        except (OSError, ValueError):
            cmd = None
        if cmd and cmd.get("seq", -1) > cmd_seq:
            cmd_seq = cmd["seq"]
            if cmd.get("kind") == "shutdown":
                return 0
            if cmd.get("kind") == "assign":
                shards = list(cmd.get("shards", []))
        if now - last_hb >= args.hb_interval:
            write_heartbeat(root, wid, hb_seq, tuple(shards),
                            jax=args.jax)
            hb_seq += 1
            last_hb = now
        try:
            task = read_json(stratum_path(root))
        except (OSError, ValueError):
            task = None
        if task and task.get("seq", -1) > last_ack_seq:
            last_ack_seq = task["seq"]
            ack = {"worker_id": wid, "seq": last_ack_seq,
                   "stratum": task.get("stratum", -1),
                   "t": time.monotonic()}
            if args.jax in ("local", "distributed"):
                ack["device_work"] = _device_work(last_ack_seq)
            write_json(ack_path(root, wid, last_ack_seq), ack)
        time.sleep(poll_s)


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="Launch-path worker process (heartbeat/lease/ack "
                    "loop, optional per-worker jax runtime).")
    parser.add_argument("--id", type=int, required=True)
    parser.add_argument("--root", required=True)
    parser.add_argument("--hb-interval", type=float, default=0.1)
    parser.add_argument("--jax", default="off", choices=JAX_MODES)
    parser.add_argument("--oneshot", action="store_true")
    parser.add_argument("--coordinator", default="")
    parser.add_argument("--num-processes", type=int, default=1)
    parser.add_argument("--process-id", type=int, default=0)
    return worker_main(parser.parse_args(argv))


if __name__ == "__main__":
    raise SystemExit(main())
