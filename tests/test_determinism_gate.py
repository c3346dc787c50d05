"""determinism gate roundtrip (scripts/ds_gate.py determinism): the CLI
against the committed DETERMINISM.json ledger.

Fast lane: subset checks (--programs serving_sample_w8 — no engine
build, the sampling program plus the AST scans and the selftest),
injected ledger regressions, and the capture/partial/missing-baseline
protocol edges. The full five-program sweep and the capture
byte-stability criterion (two captures, identical bytes) compile every
canonical train program and run in the slow lane.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEDGER = os.path.join(REPO, "DETERMINISM.json")


def _run(*args, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the script sets its own device count
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ds_gate.py"),
         "determinism", *args],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=timeout)


def _status(r):
    return json.loads(r.stderr.strip().splitlines()[-1])


class TestDsDeterminismScript:
    def test_check_passes_on_committed_tree(self):
        r = _run("--check", "--strict", "--programs", "serving_sample_w8")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = _status(r)
        assert doc == {"ok": True, "gate": "ds_determinism",
                       "strict": True}

    def test_committed_ledger_structure(self):
        doc = json.load(open(LEDGER))
        assert doc["version"] == 1
        assert set(doc["programs"]) == {
            "train_step", "train_step_moe", "train_step_pipe3d",
            "serving_decode_w8", "serving_sample_w8"}
        # the selftest counts ARE the gate's teeth: one firing per
        # seeded violation, zero on the pinned twin
        assert doc["selftest"] == {"D001": 1, "D001_pinned": 0,
                                   "D002": 1, "D003": 1, "D004": 1}
        # every registered waiver names its covering dynamic gate
        for name, entry in doc["programs"].items():
            for key, why in entry["pin"].get("waived", []):
                assert why, f"{name}: waiver {key} has no reason"
        # the sampling program's draws are in the rng ledger; the
        # greedy decode program has none
        assert doc["programs"]["serving_sample_w8"]["rng_ops"]
        assert doc["programs"]["serving_decode_w8"]["rng_ops"] == {}
        # the two annotated engine.py best-effort paths are the only
        # committed draw-key suppressions
        assert all("D004" in s for s in
                   doc["host"]["draw_keys"]["suppressed"])

    def test_check_fails_on_injected_ledger_regression(self, tmp_path):
        base = json.load(open(LEDGER))
        # erase the recorded sampling draws: the (unchanged) tree now
        # reads as "rng ops appeared in serving_sample_w8"
        base["programs"]["serving_sample_w8"]["rng_ops"] = {}
        injected = tmp_path / "determinism.json"
        injected.write_text(json.dumps(base))
        r = _run("--check", "--baseline", str(injected),
                 "--programs", "serving_sample_w8")
        assert r.returncode != 0, r.stdout + r.stderr
        assert "program ledger drift" in r.stderr
        assert "serving_sample_w8" in r.stderr

    def test_suppression_drift_warns_then_strict_fails(self, tmp_path):
        base = json.load(open(LEDGER))
        base["host"]["draw_keys"]["suppressed"].append(
            "deepspeed_tpu/inference/x.py::sample D004")
        injected = tmp_path / "determinism.json"
        injected.write_text(json.dumps(base))
        r = _run("--check", "--baseline", str(injected),
                 "--programs", "serving_sample_w8")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "suppression drift" in r.stderr
        r = _run("--check", "--strict", "--baseline", str(injected),
                 "--programs", "serving_sample_w8")
        assert r.returncode != 0, r.stdout + r.stderr

    def test_capture_refuses_partial_ledger(self, tmp_path):
        out = tmp_path / "partial.json"
        r = _run("--capture", "--baseline", str(out),
                 "--programs", "serving_sample_w8")
        assert r.returncode != 0, r.stdout + r.stderr
        assert "refusing to capture a partial ledger" in r.stderr
        assert not out.exists()

    def test_missing_baseline_is_red(self, tmp_path):
        r = _run("--check", "--baseline", str(tmp_path / "none.json"),
                 "--programs", "serving_sample_w8")
        assert r.returncode != 0, r.stdout + r.stderr
        assert "run --capture first" in r.stderr

    @pytest.mark.slow
    def test_full_check_strict(self):
        r = _run("--check", "--strict")
        assert r.returncode == 0, r.stdout + r.stderr
        assert _status(r)["ok"] is True

    @pytest.mark.slow
    def test_capture_is_byte_stable(self, tmp_path):
        """The acceptance criterion: two independent captures of the
        unchanged tree produce byte-identical ledgers (and match the
        committed one)."""
        out = tmp_path / "determinism.json"
        r = _run("--capture", "--baseline", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
        assert out.read_bytes() == open(LEDGER, "rb").read()
