#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call,
at the full width and depth of gpt-350m (d 1024, 24 layers, 16 heads of
64, d_ff 4096, vocab 32,000; random weights from a seed):

  1. trainer — `python -m kubeflow_tpu.runtime.launcher --config <yaml>`:
     5 steps, global batch 8, seq 2048, flash attention, adafactor, the
     mesh left at its default so it takes every chip the process sees.
     Passes when the launcher exits 0 and its `{"summary": ...}` line
     reports 5 steps on a TPU with a finite loss.
  2. server — `python -m kubeflow_tpu.serving --lm chat=gpt-350m
     --continuous-batching ...` (int8 weights, 16 slots, 321 pages of 16):
     one request alone (the server builds its weights and compiles inside
     it: set-up), then 8 concurrent requests with seeded random prompts
     of 32-256 tokens, two of them sharing their first 64. Passes when
     every response is HTTP 200 with 64 new tokens: the server turns any
     exception into a 400 and keeps serving, so a compile error on the
     chip looks like a healthy server.

This process never imports JAX. Each phase is one child that owns the
chip alone: the launcher runs and exits, then the server runs and is
stopped with SIGTERM and waited for. The children are told
JAX_PLATFORMS=tpu whatever this process inherited, so no phase can end up
on a CPU; with no TPU the launcher exits 69 and so does this script, with
one line saying why.

Standard output: one JSON line per phase (device, set-up seconds with
compilation included, steady seconds), then, only if every phase passed,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearse-cpu` runs the same script at a toy size on the CPU (tiny
model, Pallas interpreter) to debug the script itself before chip time
is spent; it is the only way this script runs on a CPU.
"""

import argparse
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
EX_UNAVAILABLE = 69  # the launcher's "no device of the platform asked for"
BUDGET_S = 1150.0    # the contract allows 1200 s, compilation included

FULL = dict(
    platform="tpu", model="gpt-350m", vocab=32000,
    batch=8, seq=2048, steps=5,
    slots=16, pages=321, page_size=16, prompt_len=256, new_tokens=64,
    prompt_min=32, shared=64, requests=8)
TOY = dict(
    platform="cpu", model="transformer-test", vocab=256,
    batch=8, seq=256, steps=3,
    slots=4, pages=41, page_size=16, prompt_len=64, new_tokens=8,
    prompt_min=8, shared=16, requests=8)


class Failed(Exception):
    """A phase did not pass; the message is the one line saying why."""


class NoDevice(Failed):
    """No device of the platform asked for: nothing else was tried."""


def child_env(platform: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform  # never inherited: the sandbox says cpu
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["JAXRT_METRICS_PORT"] = "0"  # any free port
    return env


def tail(text, n: int = 4000) -> str:
    """The end of a child's output (str, or the bytes a timeout leaves)."""
    if isinstance(text, bytes):
        text = text.decode("utf-8", "replace")
    return (text or "")[-n:]


# -- phase 1: the trainer ------------------------------------------------------

def run_trainer(size: dict, workdir: str, deadline: float) -> dict:
    cfg = os.path.join(workdir, "train.yaml")
    with open(cfg, "w") as f:
        f.write(
            f"model: {size['model']}\n"
            "model_kwargs:\n"
            "  attention_impl: flash\n"
            "task: lm\n"
            f"global_batch: {size['batch']}\n"
            f"seq_len: {size['seq']}\n"
            f"vocab_size: {size['vocab']}\n"
            "optimizer: adafactor\n"
            f"total_steps: {size['steps']}\n"
            "log_every: 1\n")
    cmd = [sys.executable, "-m", "kubeflow_tpu.runtime.launcher",
           "--config", cfg,
           "--wait-devices", size["platform"], "--device-timeout", "0"]
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, cwd=HERE, env=child_env(size["platform"]), text=True,
            capture_output=True, timeout=max(1.0, deadline - t0))
    except subprocess.TimeoutExpired as e:
        sys.stderr.write(tail(e.stderr))
        raise Failed("trainer: launcher still running at the time limit")
    wall = time.monotonic() - t0
    sys.stderr.write(tail(proc.stderr))
    if proc.returncode == EX_UNAVAILABLE:
        why = [ln for ln in proc.stderr.splitlines() if "devices after" in ln]
        raise NoDevice(why[-1].split("ERROR", 1)[-1].strip() if why
                       else "the launcher found no device")
    if proc.returncode != 0:
        raise Failed(f"trainer: launcher exited {proc.returncode}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith('{"summary"')]
    if not lines:
        raise Failed("trainer: launcher printed no summary line")
    s = json.loads(lines[-1])["summary"]
    dev = s.get("device") or {}
    loss = (s.get("final") or {}).get("loss")
    if dev.get("platform") != size["platform"]:
        raise Failed(f"trainer: ran on {dev}, not on {size['platform']}")
    if s.get("steps") != size["steps"] or s.get("start_step") != 0:
        raise Failed(f"trainer: took steps {s.get('start_step')}.."
                     f"{s.get('steps')}, wanted 0..{size['steps']}")
    if not isinstance(loss, float) or not math.isfinite(loss):
        raise Failed(f"trainer: final loss is {loss!r}")
    return {"phase": "trainer", "device": dev, "model": size["model"],
            "global_batch": size["batch"], "seq_len": size["seq"],
            "steps": s["steps"], "final_loss": round(loss, 4),
            "setup_s": round(s["first_step_s"], 2),
            "steady_step_s": round(s["step_time_s"], 4),
            "wall_s": round(wall, 1)}


# -- phase 2: the server -------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None, timeout: float):
    """(status, parsed JSON or text). Never raises on an HTTP status."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")[:300]


def make_prompts(size: dict) -> list[list[int]]:
    """One set-up prompt, then `requests` prompts of prompt_min..prompt_len
    tokens from a seed. The last two share their first `shared` tokens and
    are full length: prompts are left-padded to prompt_len, so only equal
    lengths put a shared prefix on the same pages."""
    rng = random.Random(0)

    def draw(n):
        return [rng.randrange(1, size["vocab"]) for _ in range(n)]

    prompts = [draw(rng.randint(size["prompt_min"], size["prompt_len"]))
               for _ in range(1 + size["requests"] - 2)]
    common = draw(size["shared"])
    for _ in range(2):
        prompts.append(common + draw(size["prompt_len"] - size["shared"]))
    return prompts


def run_server(size: dict, workdir: str, deadline: float) -> dict:
    port = free_port()
    base = f"http://127.0.0.1:{port}/v1/models/chat"
    cmd = [sys.executable, "-m", "kubeflow_tpu.serving",
           "--lm", f"chat={size['model']}", "--continuous-batching",
           "--decode-slots", str(size["slots"]),
           "--kv-pages", str(size["pages"]),
           "--kv-page-size", str(size["page_size"]),
           "--prompt-len", str(size["prompt_len"]),
           "--max-new-tokens", str(size["new_tokens"]),
           "--param-dtype", "int8", "--port", str(port)]
    log_path = os.path.join(workdir, "server.log")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=child_env(size["platform"]),
                                stdout=log, stderr=subprocess.STDOUT)
    try:
        return _drive_server(size, proc, base, t0, deadline)
    finally:
        # SIGTERM and wait: no chip lock may outlive this script
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
        with open(log_path) as f:
            sys.stderr.write(tail(f.read()))


def _drive_server(size, proc, base, t0, deadline) -> dict:
    def left() -> float:
        return max(1.0, deadline - time.monotonic())

    # the server takes its device before it listens
    meta = None
    while meta is None:
        if proc.poll() is not None:
            raise Failed(f"server: exited {proc.returncode} before listening")
        if time.monotonic() > min(deadline, t0 + 180):
            raise Failed("server: not listening after 180 s")
        try:
            status, meta = http_json(base + "/metadata", None, timeout=5)
            if status != 200:
                raise Failed(f"server: metadata answered {status}: {meta}")
        except (urllib.error.URLError, OSError):
            time.sleep(1.0)
    listen_s = time.monotonic() - t0
    dev = meta.get("device") or {}
    if dev.get("platform") != size["platform"]:
        raise Failed(f"server: runs on {dev}, not on {size['platform']}")

    def ask(tokens, timeout):
        """(status, new tokens or what went wrong, seconds); status 0 =
        no answer (timeout, reset)."""
        t = time.monotonic()
        try:
            status, doc = http_json(
                base + ":predict", {"instances": [{"tokens": tokens}]}, timeout)
        except (urllib.error.URLError, OSError) as e:
            status, doc = 0, repr(e)
        got = (doc["predictions"][0] if status == 200
               and isinstance(doc, dict) else doc)
        return status, got, time.monotonic() - t

    def check(i, status, got):
        if status != 200:
            raise Failed(f"server: request {i} answered {status}: {got}")
        if (len(got) != size["new_tokens"] or not all(
                isinstance(t, int) and 0 <= t < size["vocab"] for t in got)):
            raise Failed(f"server: request {i} returned {len(got)} tokens, "
                         f"wanted {size['new_tokens']} in [0, {size['vocab']})")

    prompts = make_prompts(size)
    status, got, setup_s = ask(prompts[0], left())
    check(0, status, got)

    results: list = [None] * size["requests"]

    def worker(i):
        results[i] = ask(prompts[1 + i], left())

    t1 = time.monotonic()
    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(size["requests"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=left())
    steady_s = time.monotonic() - t1
    for i, r in enumerate(results):
        if r is None:
            raise Failed(f"server: request {1 + i} unanswered at the time limit")
        check(1 + i, r[0], r[1])
    return {"phase": "server", "device": dev, "model": size["model"],
            "param_dtype": "int8", "decode_slots": size["slots"],
            "kv_pages": size["pages"], "requests_ok": 1 + size["requests"],
            "new_tokens_each": size["new_tokens"],
            "listen_s": round(listen_s, 1), "setup_s": round(setup_s, 2),
            "steady_s": round(steady_s, 2),
            "slowest_request_s": round(max(r[2] for r in results), 2)}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="toy size on the CPU, to debug this script; the "
                        "plain invocation runs on a TPU or not at all")
    args = p.parse_args()
    size = TOY if args.rehearse_cpu else FULL
    deadline = time.monotonic() + BUDGET_S
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke.") as workdir:
            phases = []
            for run in (run_trainer, run_server):
                phases.append(run(size, workdir, deadline))
                print(json.dumps(phases[-1]), flush=True)
    except NoDevice as e:
        print(f"chip_smoke: FAIL: no {size['platform']} found: {e}",
              file=sys.stderr)
        return EX_UNAVAILABLE
    except Failed as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    devices = [ph["device"] for ph in phases]
    if devices[0] != devices[1]:
        print(f"chip_smoke: FAIL: the phases ran on different devices: "
              f"{devices}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
