"""hvdrun — the launcher.

Replacement for the reference's `mpirun -np N python train.py` contract
(`README.md:125-135`, SURVEY §7 step 6): spawns N worker processes,
wires the process group (rank/size/local placement env vars), runs the
native TCP rendezvous (key-value + barrier) that replaces the MPI
control plane, and points workers at a JAX coordination service for
`jax.distributed.initialize`.

Usage:
    hvdrun -np 4 python train.py ...
    hvdrun -np 2 --platform cpu python train.py

Multi-host (the reference's `mpirun -H server1:4,server2:4` contract,
`README.md:136-144`): run one hvdrun per host with the same slot map.
Host 0 serves the shared rendezvous; the others point at it:

    # on server1 (hosts rank 0; serves the KV/barrier plane)
    hvdrun -H server1:4,server2:4 --host-index 0 --kv-port 29500 \
           python train.py
    # on server2
    hvdrun -H server1:4,server2:4 --host-index 1 \
           --rendezvous server1:29500 python train.py

Each instance launches only its own host's slots with global rank
offsets; the env-var contract (HOROVOD_RANK / SIZE / LOCAL_RANK /
LOCAL_SIZE / COORDINATOR / KV) is identical either way. (TPU pods
usually skip hvdrun entirely: the pod runtime provides the process
group and `hvd.init()` attaches to it.)
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
from typing import List


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stream(prefix: str, pipe, out):
    for line in iter(pipe.readline, ""):
        out.write(f"[{prefix}] {line}")
        out.flush()
    pipe.close()


def _parse_hosts(spec: str):
    """'server1:4,server2:4' -> [('server1', 4), ('server2', 4)]
    (reference `mpirun -H` slot syntax, README.md:136-144)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, sep, slots = part.partition(":")
        n = int(slots) if sep else 1
        if not host or n < 1:
            raise ValueError(f"bad host entry {part!r} (need host:n "
                             f"with n >= 1)")
        out.append((host, n))
    if not out:
        raise ValueError(f"empty host spec {spec!r}")
    return out


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch N horovod_tpu worker processes (mpirun "
                    "replacement).")
    ap.add_argument("-np", "--num-proc", type=int, default=None,
                    help="total worker processes across all hosts "
                         "(default: sum of -H slots)")
    ap.add_argument("-H", "--hosts", default=None,
                    help="host1:n,host2:n slot map; this instance "
                         "launches only the --host-index entry's slots "
                         "with global rank offsets")
    ap.add_argument("--host-index", type=int, default=0,
                    help="which -H entry this instance is")
    ap.add_argument("--rendezvous", default=None, metavar="HOST:PORT",
                    help="KV/barrier server of host 0 (non-zero hosts "
                         "connect instead of serving)")
    ap.add_argument("--kv-port", type=int, default=0,
                    help="fixed port for the rendezvous server on host "
                         "0 (default: any free port)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address; must be "
                         "the same on every host (default: a free port "
                         "on this host — fine single-host)")
    ap.add_argument("--platform", default="cpu",
                    choices=["cpu", "tpu", "auto"],
                    help="JAX platform forced in workers (cpu default: "
                         "several workers on one host get virtual CPU "
                         "devices; a host's TPU chips belong to ONE "
                         "process, so `tpu` takes one worker per "
                         "host)")
    ap.add_argument("--devices-per-proc", type=int, default=1,
                    help="virtual CPU devices per worker (cpu platform)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic job: a worker killed by a SIGNAL "
                         "(preemption, the elastic drill's SIGKILL) "
                         "does NOT kill the job — survivors keep "
                         "running and the exit code reflects them; a "
                         "worker failing with a nonzero STATUS still "
                         "fails the job (mpirun's all-or-nothing "
                         "contract stays the default)")
    ap.add_argument("--no-prefix", action="store_true",
                    help="don't prefix worker output with [rank]")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="worker command, e.g. python train.py")
    args = ap.parse_args(argv)

    if not args.command:
        ap.error("missing worker command")
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]

    # Resolve this instance's slice of the world.
    if args.hosts is not None:
        try:
            hosts = _parse_hosts(args.hosts)
        except ValueError as e:
            ap.error(str(e))
        total = sum(s for _, s in hosts)
        if args.num_proc is not None and args.num_proc != total:
            ap.error(f"-np {args.num_proc} != sum of -H slots {total}")
        if not 0 <= args.host_index < len(hosts):
            ap.error(f"--host-index {args.host_index} out of range for "
                     f"{len(hosts)} hosts")
        rank_offset = sum(s for _, s in hosts[:args.host_index])
        local_n = hosts[args.host_index][1]
        head_host = hosts[0][0]
    else:
        if args.num_proc is None:
            ap.error("need -np or -H")
        if args.host_index != 0 or args.rendezvous is not None:
            # Without a slot map there are no rank offsets: a second
            # instance would relaunch ranks 0..n-1 and corrupt the
            # process group.
            ap.error("--host-index/--rendezvous require -H")
        total = local_n = args.num_proc
        rank_offset = 0
        head_host = "127.0.0.1"

    if args.platform == "tpu" and local_n > 1:
        # Every worker would see (and try to take) all of this host's
        # chips; a chip belongs to one process at a time.
        ap.error(f"--platform tpu with {local_n} workers on one host: "
                 f"a host's TPU chips belong to ONE process. Run the "
                 f"script directly (`python train.py`: one process "
                 f"drives all local devices, README.md) "
                 f"or launch one worker per host (-H host1:1,host2:1)")

    serve_here = args.rendezvous is None and args.host_index == 0
    if (args.hosts is not None and len(hosts) > 1
            and not args.coordinator):
        # Each instance would pick an independent random port for the
        # jax.distributed coordinator — guaranteed cross-host hang.
        ap.error("multi-host launch requires --coordinator HOST:PORT "
                 "(the same value on every host)")
    coord_addr = args.coordinator or f"{head_host}:{_free_port()}"

    native = None
    if serve_here:
        # The launcher hosts the rendezvous server (the rank-0
        # coordinator role of the reference's background thread,
        # mpi_ops.cc:1316-1371). Barrier membership is the TOTAL world,
        # so multi-host instances meet at the same server.
        from horovod_tpu.native import load_native
        native = load_native()
        bound = native.serve(args.kv_port or _free_port(), total)
        if bound <= 0:
            print("hvdrun: failed to start rendezvous server",
                  file=sys.stderr)
            return 1
        kv_addr = f"{head_host}:{bound}" if args.hosts else \
            f"127.0.0.1:{bound}"
        if args.hosts is not None and len(hosts) > 1:
            # Other hosts must be pointed at this exact address; with
            # an ephemeral port (no --kv-port) they can't guess it.
            print(f"hvdrun: rendezvous serving at {kv_addr} — start "
                  f"the other hosts with --rendezvous {kv_addr}",
                  file=sys.stderr)
            if not args.kv_port:
                print("hvdrun: warning: no --kv-port given; the port "
                      "above is ephemeral and differs every run",
                      file=sys.stderr)
    else:
        if args.rendezvous is None:
            ap.error("non-zero --host-index needs --rendezvous "
                     "(host 0's KV address)")
        kv_addr = args.rendezvous

    procs: List[subprocess.Popen] = []
    threads: List[threading.Thread] = []
    for local_rank in range(local_n):
        rank = rank_offset + local_rank
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(total),
            "HOROVOD_LOCAL_RANK": str(local_rank),
            "HOROVOD_LOCAL_SIZE": str(local_n),
            "HOROVOD_COORDINATOR": coord_addr,
            "HOROVOD_KV": kv_addr,
        })
        if args.platform != "auto":
            env["HOROVOD_PLATFORM"] = args.platform
        if args.platform == "cpu":
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                        f"{args.devices_per_proc}").strip()
        p = subprocess.Popen(
            cmd, env=env,
            stdout=subprocess.PIPE if not args.no_prefix else None,
            stderr=subprocess.STDOUT if not args.no_prefix else None,
            text=not args.no_prefix)
        procs.append(p)
        if not args.no_prefix:
            t = threading.Thread(target=_stream,
                                 args=(str(rank), p.stdout, sys.stdout),
                                 daemon=True)
            t.start()
            threads.append(t)

    exit_code = 0
    clean_exits = 0
    try:
        remaining = set(range(local_n))
        while remaining:
            for i in list(remaining):
                rc = procs[i].poll()
                if rc is None:
                    continue
                remaining.discard(i)
                if rc == 0:
                    clean_exits += 1
                    continue
                if args.elastic and rc < 0:
                    # Elastic contract: a signal death (preemption,
                    # SIGKILL drill) is a MEMBERSHIP event, not a job
                    # failure — the survivors' resize protocol owns
                    # it from here.
                    print(f"hvdrun: worker {rank_offset + i} died "
                          f"with signal {-rc}; elastic job continues",
                          file=sys.stderr)
                    continue
                if exit_code == 0:
                    exit_code = rc
                    if not args.elastic:
                        # mpirun behavior: one failure kills the job.
                        for j in remaining:
                            procs[j].terminate()
            if remaining:
                import time
                time.sleep(0.2)
        if args.elastic and exit_code == 0 and clean_exits == 0:
            # Every worker died by signal: nobody survived to finish
            # the job — that is a failure, not elasticity.
            exit_code = 1
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        exit_code = 130
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for t in threads:
            t.join(timeout=2)
        if native is not None:
            native.serve_stop()
    return exit_code
