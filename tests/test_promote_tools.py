"""Promotion tooling: the unattended sweep's bank-the-best discipline.

These scripts decide what the driver's round-end bench replays, so their
invariants get their own tests: only measured points promote, windowed
points never win the LM headline, serving A/B pairs never collapse into
one table row, and non-default geometries never raise the headline
floor."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tool, args, cwd):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", tool), *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def _load(path):
    return json.load(open(path))


class TestPromoteServeBest:
    def _write_log(self, tmp_path, docs):
        p = tmp_path / "serve.out"
        p.write_text("\n".join(json.dumps(d) for d in docs) + "\n")
        return str(p)

    def _tool_env(self, tmp_path):
        # run the tool from a temp copy so serve_best.json lands there
        import shutil

        tooldir = tmp_path / "tools"
        tooldir.mkdir()
        for f in ("promote_serve_best.py",):
            shutil.copy(os.path.join(REPO, "tools", f), tooldir / f)
        return tooldir

    def _doc(self, **over):
        base = dict(mode="continuous", model="gpt-350m", max_new_tokens=32,
                    slots=8, param_dtype="int8", tokens_per_sec=100.0,
                    requests=16, p50_ms=10.0)
        base.update(over)
        return base

    def test_window_ab_pair_keeps_both_rows(self, tmp_path):
        tooldir = self._tool_env(tmp_path)
        log = self._write_log(tmp_path, [
            self._doc(model="llama-1b", attention_window=512,
                      rolling_kv_cache=False, tokens_per_sec=80.0),
            self._doc(model="llama-1b", attention_window=512,
                      rolling_kv_cache=True, tokens_per_sec=120.0),
        ])
        r = subprocess.run([sys.executable, str(tooldir / "promote_serve_best.py"),
                            log], capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        table = _load(tooldir / "serve_table.json")
        assert len(table) == 2, table  # the A/B must not collapse

    def test_non_default_geometry_never_wins_headline(self, tmp_path):
        tooldir = self._tool_env(tmp_path)
        log = self._write_log(tmp_path, [
            self._doc(model="llama-1b", tokens_per_sec=999.0),
            self._doc(model="gpt-350m", tokens_per_sec=50.0),
        ])
        subprocess.run([sys.executable, str(tooldir / "promote_serve_best.py"),
                        log], capture_output=True, text=True, timeout=120)
        best = _load(tooldir / "serve_best.json")
        assert best["model"] == "gpt-350m"
        assert best["tokens_per_sec"] == 50.0

    def test_micro_mode_lines_ignored(self, tmp_path):
        tooldir = self._tool_env(tmp_path)
        log = self._write_log(tmp_path, [
            self._doc(mode="micro", tokens_per_sec=500.0),
        ])
        subprocess.run([sys.executable, str(tooldir / "promote_serve_best.py"),
                        log], capture_output=True, text=True, timeout=120)
        assert not (tooldir / "serve_best.json").exists()


class TestPromoteBest:
    def test_windowed_points_never_promote(self, tmp_path):
        import shutil

        tooldir = tmp_path / "tools"
        tooldir.mkdir()
        shutil.copy(os.path.join(REPO, "tools", "promote_best.py"),
                    tooldir / "promote_best.py")
        log = tmp_path / "sweep.log"
        log.write_text(json.dumps({"lm": {
            "model": "gpt-350m", "mfu": 0.99, "window": 512,
            "optimizer": "adafactor", "tokens_per_sec": 1,
        }}) + "\n")
        subprocess.run([sys.executable, str(tooldir / "promote_best.py"),
                        str(log)], capture_output=True, text=True, timeout=120)
        assert not (tooldir / "lm_best.json").exists()

    def test_floor_from_existing_best_blocks_weaker_point(self, tmp_path):
        import shutil

        tooldir = tmp_path / "tools"
        tooldir.mkdir()
        shutil.copy(os.path.join(REPO, "tools", "promote_best.py"),
                    tooldir / "promote_best.py")
        (tooldir / "lm_best.json").write_text(json.dumps(
            {"model": "gpt-350m", "mfu": 0.4936, "optimizer": "adafactor"}))
        log = tmp_path / "sweep.log"
        log.write_text(json.dumps({"lm": {
            "model": "gpt-350m", "mfu": 0.40, "optimizer": "adafactor",
            "tokens_per_sec": 1,
        }}) + "\n")
        subprocess.run([sys.executable, str(tooldir / "promote_best.py"),
                        str(log)], capture_output=True, text=True, timeout=120)
        # the weaker measured point must NOT replace the banked best
        assert _load(tooldir / "lm_best.json")["mfu"] == 0.4936

    def test_promoted_record_drops_stale_remat_policy(self, tmp_path):
        """Ledger hygiene: a winning point with
        remat=false must not carry a remat_policy field — the knob never
        ran, and recording it invites reading the number as
        remat-verified."""
        import shutil

        tooldir = tmp_path / "tools"
        tooldir.mkdir()
        shutil.copy(os.path.join(REPO, "tools", "promote_best.py"),
                    tooldir / "promote_best.py")
        log = tmp_path / "sweep.log"
        log.write_text(json.dumps({"lm": {
            "model": "gpt-350m", "mfu": 0.52, "optimizer": "adafactor",
            "remat": False, "remat_policy": "mlp", "tokens_per_sec": 1,
        }}) + "\n" + json.dumps({"lm": {
            "model": "gpt-350m", "mfu": 0.30, "optimizer": "adafactor",
            "remat": True, "remat_policy": "dots", "tokens_per_sec": 1,
        }}) + "\n")
        subprocess.run([sys.executable, str(tooldir / "promote_best.py"),
                        str(log)], capture_output=True, text=True,
                       timeout=120)
        best = _load(tooldir / "lm_best.json")
        assert best["mfu"] == 0.52
        assert "remat_policy" not in best

    def test_promoted_record_keeps_policy_when_remat_ran(self, tmp_path):
        import shutil

        tooldir = tmp_path / "tools"
        tooldir.mkdir()
        shutil.copy(os.path.join(REPO, "tools", "promote_best.py"),
                    tooldir / "promote_best.py")
        log = tmp_path / "sweep.log"
        log.write_text(json.dumps({"lm": {
            "model": "llama-1b", "mfu": 0.55, "optimizer": "adafactor",
            "remat": True, "remat_policy": "dots", "tokens_per_sec": 1,
        }}) + "\n")
        subprocess.run([sys.executable, str(tooldir / "promote_best.py"),
                        str(log)], capture_output=True, text=True,
                       timeout=120)
        best = _load(tooldir / "lm_best.json")
        assert best["remat_policy"] == "dots"
