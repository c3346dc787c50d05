"""Multi-process launcher.

TPU-native analog of the reference launcher stack
(ref: launcher/runner.py main:388 → multinode_runner.py PDSH/MPI/Slurm →
launcher/launch.py main:132 per-node spawner with per-rank env +
terminate_process_tree:118). On TPU pods the heavy half disappears: the
TPU runtime already starts one process per host with coordinator env set
— `deepspeed_tpu.comm.init_distributed()` picks it up, so "launching" a
pod job is just running the script on every host (gcloud ... --worker=all).

What remains useful — and is implemented here — is the LOCAL spawner:
run N controller processes on one machine, each with a slice of fake
CPU devices, for the multi-process tests. It is NOT how a TPU host is
driven: ONE process drives all of a host's chips (a chip belongs to one
process at a time, and this spawner gives ranks no per-rank chip
visibility, so on a TPU host every rank would try to open every chip).
It assigns a free coordinator port, sets MASTER_ADDR/PORT +
RANK/WORLD_SIZE per rank (the env contract init_distributed consumes),
prefixes each rank's output, and kills the whole tree if any rank dies
(the launch.py sigkill semantics).

Usage:
  python -m deepspeed_tpu.launcher --num_procs 2 \
      [--devices_per_proc 4] your_script.py --your-args
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
from typing import List


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _stream(proc: subprocess.Popen, rank: int) -> None:
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[rank{rank}] {line}")
        sys.stdout.flush()


def launch_local(
    cmd: List[str],
    num_procs: int,
    devices_per_proc: int = 0,
    env_extra=None,
    timeout_s: float = 0,
) -> int:
    """Spawn `num_procs` copies of cmd with the distributed env contract.
    Returns the first nonzero exit code (0 if all succeeded; 124 on
    timeout — the test-harness hang-kill, ref: tests/unit/common.py:165)."""
    port = str(_free_port())
    procs: List[subprocess.Popen] = []
    threads: List[threading.Thread] = []
    for rank in range(num_procs):
        env = dict(os.environ)
        env.update(env_extra or {})
        env["MASTER_ADDR"] = "127.0.0.1"
        env["MASTER_PORT"] = port
        env["WORLD_SIZE"] = str(num_procs)
        env["RANK"] = str(rank)
        env["LOCAL_RANK"] = str(rank)  # reference env contract (launch.py)
        if devices_per_proc:
            env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={devices_per_proc}"
            )
        p = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        procs.append(p)
        t = threading.Thread(target=_stream, args=(p, rank), daemon=True)
        t.start()
        threads.append(t)

    def _terminate_all(*_):
        # ref: launch.py terminate_process_tree:118
        for p in procs:
            if p.poll() is None:
                p.terminate()

    old = signal.signal(signal.SIGINT, _terminate_all)
    try:
        import time

        rc = 0
        deadline = time.monotonic() + timeout_s if timeout_s else None
        # poll so one dead rank kills the whole tree instead of leaving
        # the survivors blocked in rendezvous (ref: launch.py main loop +
        # terminate_process_tree:118)
        while True:
            if deadline is not None and time.monotonic() > deadline:
                print("[launcher] timeout; terminating all ranks",
                      file=sys.stderr)
                rc = 124
                _terminate_all()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                break
            codes = [p.poll() for p in procs]
            failed = [(i, c) for i, c in enumerate(codes) if c not in (None, 0)]
            if failed:
                rank, rc = failed[0]
                print(f"[launcher] rank {rank} exited with {rc}; "
                      "terminating remaining ranks", file=sys.stderr)
                _terminate_all()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                break
            if all(c is not None for c in codes):
                break
            time.sleep(0.2)
        for t in threads:
            t.join(timeout=5)
        return rc
    finally:
        signal.signal(signal.SIGINT, old)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument("--num_procs", type=int, default=1,
                        help="controller processes to spawn on this host")
    parser.add_argument("--devices_per_proc", type=int, default=0,
                        help="virtual CPU devices per process (testing)")
    parser.add_argument("--module", "-m", action="store_true",
                        help="run script as a python module")
    parser.add_argument("script", help="training script (SPMD: runs on every rank)")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    cmd = [sys.executable]
    if args.module:
        cmd.append("-m")
    cmd.append(args.script)
    cmd.extend(args.script_args)
    return launch_local(cmd, args.num_procs, args.devices_per_proc)


if __name__ == "__main__":
    sys.exit(main())
