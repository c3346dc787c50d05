"""Launcher + env-report tests (ref: tests/unit/launcher)."""

import json
import os
import subprocess
import sys

import numpy as np

from deepspeed_tpu.launcher.runner import launch_local


def test_env_report_runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.env_report"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "op compatibility" in out.stdout
    assert "async_io" in out.stdout
    assert "device count" in out.stdout, out.stdout


def test_launch_local_spawns_world(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, jax\n"
        "jax.config.update('jax_platforms','cpu')\n"
        "import deepspeed_tpu as ds\n"
        "ds.comm.init_distributed()\n"
        "assert ds.comm.get_process_count() == 2\n"
        "assert ds.comm.get_world_size() == 4\n"
        "print(f'rank {os.environ[\"RANK\"]} sees world '\n"
        "      f'{ds.comm.get_world_size()}')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc = launch_local(
        [sys.executable, str(script)], num_procs=2, devices_per_proc=2,
        env_extra={"PYTHONPATH": repo},
    )
    assert rc == 0


def test_launch_local_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)\n")
    rc = launch_local([sys.executable, str(script)], num_procs=2)
    assert rc == 3


class TestPodLauncher:
    """Pod fan-out CLI (ref: launcher/runner.py:388 + multinode_runner
    PDSHRunner) — command assembly + per-worker log aggregation, driven
    against a stub gcloud (the real one needs a pod)."""

    def test_command_assembly(self):
        from deepspeed_tpu.launcher.pod import build_worker_command

        cmd = build_worker_command(
            "slice-a", "us-east5-a", ["python", "train.py", "--lr", "1e-4"],
            worker="all", project="proj",
            env={"JAX_X": "1", "A": "b c"}, chdir="/work")
        assert cmd[:6] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh",
                           "slice-a"]
        assert "--project=proj" in cmd and "--zone=us-east5-a" in cmd
        assert "--worker=all" in cmd
        inner = cmd[-1]
        assert inner.startswith("export A='b c'; export JAX_X=1; ")
        assert "cd /work && python train.py --lr 1e-4" in inner

    def _stub_gcloud(self, tmp_path):
        stub = tmp_path / "gcloud"
        stub.write_text(
            "#!/bin/sh\n"
            "# echo the worker flag + run the --command locally\n"
            'for a in "$@"; do case "$a" in --worker=*) W=${a#--worker=};;'
            " esac; done\n"
            'CMD=""\n'
            'prev=""\n'
            'for a in "$@"; do if [ "$prev" = "--command" ]; then CMD="$a";'
            ' fi; prev="$a"; done\n'
            'echo "hello from worker $W"\n'
            'sh -c "$CMD"\n')
        stub.chmod(0o755)
        return str(stub)

    def test_per_worker_logs_and_exit(self, tmp_path, capsys):
        from deepspeed_tpu.launcher.pod import run_on_pod

        rc = run_on_pod(
            "s", "z", ["echo", "ran"], workers="0,1",
            log_dir=str(tmp_path / "logs"), gcloud=self._stub_gcloud(tmp_path))
        assert rc == 0
        out = capsys.readouterr().out
        assert "[worker 0] hello from worker 0" in out
        assert "[worker 1] hello from worker 1" in out
        for w in ("0", "1"):
            log = (tmp_path / "logs" / f"worker_{w}.log").read_text()
            assert f"hello from worker {w}" in log and "ran" in log

    def test_failure_propagates(self, tmp_path):
        from deepspeed_tpu.launcher.pod import run_on_pod

        rc = run_on_pod("s", "z", ["sh", "-c", "exit 3"], workers="all",
                        gcloud=self._stub_gcloud(tmp_path))
        assert rc == 3

    def test_cli_env_report_spelling(self, tmp_path, capsys):
        from deepspeed_tpu.launcher.pod import main

        rc = main(["--tpu", "s", "--zone", "z",
                   "--gcloud", self._stub_gcloud(tmp_path), "--",
                   "echo", "ok"])
        assert rc == 0
        assert "ok" in capsys.readouterr().out


class TestCommBench:
    """ds_bench analog (ref: bin/ds_bench → benchmarks/communication/):
    the sweep must run every op on the virtual mesh and report busbw
    with the reference's ring-correction convention."""

    def test_sweep_all_ops(self):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.comm.bench import OPS, _busbw_factor, sweep

        records = sweep(list(OPS), [64 * 1024], trials=2,
                        dtype=jnp.float32)
        assert {r["op"] for r in records} == set(OPS)
        n = len(jax.devices())
        for r in records:
            assert r["devices"] == n
            assert r["bytes_per_device"] > 0
            assert r["algbw_GBps"] > 0
            np.testing.assert_allclose(
                r["busbw_GBps"],
                r["algbw_GBps"] * _busbw_factor(r["op"], n))

    def test_busbw_convention(self):
        from deepspeed_tpu.comm.bench import _busbw_factor

        # ref benchmarks/communication/utils.py busbw notes
        assert _busbw_factor("all_reduce", 8) == 2 * 7 / 8
        assert _busbw_factor("all_gather", 8) == 7 / 8
        assert _busbw_factor("ppermute", 8) == 1.0

    def test_cli_json_line(self, capsys):
        from deepspeed_tpu.comm.bench import main

        rc = main(["--ops", "all_gather", "--sizes-mb", "0.0625",
                   "--trials", "1", "--dtype", "float32", "--json"])
        assert rc == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        rec = json.loads(line)["ds_bench"]
        assert rec[0]["op"] == "all_gather"
