"""What keeps the main path honest about the device it runs on.

The chip is reached through chip_smoke.py, never through pytest, so these
run on the CPU and check the two things a CPU can: that the flash kernel
LOWERS for the TPU under a mesh (interpret mode off — the interpreter is
plain jnp, which GSPMD partitions happily and no test ever saw fail), and
that nothing on the way to the chip quietly settles for a CPU.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops import flash_attention as FA
from kubeflow_tpu.ops.attention import attention
from kubeflow_tpu.parallel.mesh import BATCH_AXES, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESHES = [{"data": 4}, {"fsdp": 2, "model": 2}]
TPU = dict(lowering_platforms=("tpu",))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the Mosaic kernel, not the interpreter, on this CPU host."""
    monkeypatch.setattr(FA, "INTERPRET", False)


@pytest.mark.parametrize("spec", MESHES, ids=str)
def test_flash_fwd_bwd_lowers_for_tpu_under_mesh(spec, devices8,
                                                 compiled_kernels):
    mesh = build_mesh(spec, devices8[:4])
    q = jax.ShapeDtypeStruct(
        (4, 256, 4, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(BATCH_AXES, None, "model", None)))

    def loss(q, k, v):
        return attention(q, k, v, impl="flash").astype(jnp.float32).sum()

    with mesh:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(
            q, q, q).lower(**TPU).as_text()
    # forward, dq, dk/dv
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("spec", MESHES, ids=str)
def test_flash_train_step_lowers_for_tpu_under_mesh(spec, devices8,
                                                    compiled_kernels):
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    cfg = TrainConfig.from_dict(dict(
        model="transformer-test",
        model_kwargs={"attention_impl": "flash", "head_dim": 64},
        task="lm", global_batch=8, seq_len=128, vocab_size=256,
        optimizer="adafactor", mesh=spec))
    trainer = Trainer(cfg, mesh=build_mesh(spec, devices8[:4]))
    with trainer.mesh:
        text = trainer._train_step.trace(
            trainer.abstract_state, trainer.abstract_batch).lower(
            **TPU).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec", MESHES, ids=str)
def test_flash_under_mesh_matches_reference(spec, devices8):
    """The shard_map wrapper's specs (batch over BATCH_AXES, heads over
    `model`, GQA with KV heads that do and do not divide `model`, packed
    segments) against the reference, values and gradients; the kernel
    itself is the interpreter here."""
    from conftest import make_segments
    from kubeflow_tpu.ops.attention import reference_attention

    mesh = build_mesh(spec, devices8[:4])
    seg = make_segments(4, 128, 3)
    for hkv in (2, 1):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(hkv), 3)
        q = jax.random.normal(kq, (4, 128, 4, 16), jnp.float32)
        k = jax.random.normal(kk, (4, 128, hkv, 16), jnp.float32)
        v = jax.random.normal(kv, (4, 128, hkv, 16), jnp.float32)

        def grads(fn):
            return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                            argnums=(0, 1, 2))

        with mesh:
            got = jax.jit(grads(lambda q, k, v: attention(
                q, k, v, impl="flash", segment_ids=seg)))(q, k, v)
        want = grads(lambda q, k, v: reference_attention(
            q, k, v, segment_ids=seg))(q, k, v)
        for g, w in zip(got, want):
            assert jnp.allclose(g, w, atol=1e-4), (spec, hkv)


def test_interpret_mode_is_decided_once_and_said(monkeypatch, caplog):
    monkeypatch.setattr(FA, "INTERPRET", None)
    with caplog.at_level("WARNING", logger="kubeflow_tpu.flash_attention"):
        assert FA.interpret_mode() is True      # the CPU backend
        assert FA.interpret_mode() is True
    assert caplog.text.count("INTERPRET mode") == 1


def test_auto_attention_says_what_it_chose(caplog):
    from kubeflow_tpu.models.registry import get_model

    with caplog.at_level("INFO", logger="kubeflow_tpu.attention"):
        model = get_model("transformer-test")
    assert model.cfg.attention_impl == "reference"
    assert "auto -> reference" in caplog.text and "'cpu'" in caplog.text


def test_auto_attention_has_one_rule(monkeypatch):
    """Backend and head size, nothing else: no second look at the
    sequence length further down that could quietly swap the kernel for
    the [B, H, L, L] reference. An awkward length raises instead."""
    from kubeflow_tpu.ops.attention import local_attention, resolve_impl

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl("auto", 64) == "flash"
    assert resolve_impl("auto", 16) == "reference"
    assert resolve_impl("ring", 16) == "ring"
    q = jnp.zeros((1, 1000, 2, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiples of the block"):
        attention(q, q, q, impl="auto")
    with pytest.raises(ValueError, match="unknown attention impl 'auto'"):
        local_attention(q, q, q, impl="auto")


def test_unknown_device_has_no_peak():
    from kubeflow_tpu.runtime import metrics as M

    assert M.peak_flops("TPU v5 lite") == 197e12
    assert M.peak_hbm_bw("TPU v5 lite") == 819e9
    for lookup in (M.peak_flops, M.peak_hbm_bw):
        with pytest.raises(M.UnknownDeviceError):
            lookup("cpu")
    assert M.StepMeter(1e12, 1, "cpu").peak is None


def test_wait_for_tpu_is_not_satisfied_by_cpu_devices():
    from kubeflow_tpu.runtime import launcher

    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(TimeoutError, match="no tpu devices"):
        launcher.wait_for_devices("tpu", timeout_s=0)


def test_every_caller_of_wait_devices_names_a_platform(tmp_path):
    """--wait-devices takes a PLATFORM; a caller left with the bare flag
    dies in argparse before it joins the gang. The runtime image's
    ENTRYPOINT goes through launcher.main as a pod would run it (args
    appended), and no source, doc or manifest in the checkout shows the
    flag without a value."""
    import json
    import re

    from kubeflow_tpu.runtime import launcher

    with open(os.path.join(REPO, "images", "jaxrt", "Dockerfile")) as f:
        entry = json.loads(
            re.search(r"^ENTRYPOINT (\[.*\])$", f.read(), re.M).group(1))
    assert entry[:3] == ["python", "-m", "kubeflow_tpu.runtime.launcher"]
    # argparse would leave by SystemExit(2); 69 = parsed, asked for the
    # TPU by name, did not take this machine's CPU for one, and left
    # with the launcher's own exit code, config unread
    rc = launcher.main(entry[3:] + ["--device-timeout", "0", "--config",
                                    str(tmp_path / "absent.yaml")])
    assert rc == launcher.EX_UNAVAILABLE

    flag = "--wait-" + "devices"        # so this file passes its own sweep
    skip = {".git", ".jax_cache", ".scratch", "chiprun_out", "__pycache__",
            "node_modules"}
    bare = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in files:
            if not name.endswith((".py", ".md", ".yaml", ".yml", ".sh",
                                  ".json", "Dockerfile")):
                continue
            if root == REPO and name in ("ISSUE.md", "REVIEW.md"):
                continue                # the driver's files
            with open(os.path.join(root, name), encoding="utf-8",
                      errors="replace") as f:
                text = f.read()
            # a value is a word, quoted or not, right after the flag
            bare += [f"{os.path.join(root, name)}: {m.group(0)!r}"
                     for m in re.finditer(
                         flag + r"""\b(?!["'`]?,?\s*["'`]?[A-Za-z])[^\n]{0,20}""",
                         text)]
    assert not bare, bare


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch, tmp_path):
    from kubeflow_tpu.utils import compile_cache

    # placed from outside: JAX reads the variable itself, nothing is set
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # not placed: two processes, wherever they start, agree on one path
    # inside the checkout
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    env["PYTHONPATH"] = REPO
    code = ("from kubeflow_tpu.utils import compile_cache as c; import jax; "
            "assert c.configure() == jax.config.jax_compilation_cache_dir; "
            "print(c.configure())")
    seen = {subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, check=True,
                           timeout=120).stdout.strip()
            for cwd in (REPO, str(tmp_path))}
    assert seen == {os.path.join(REPO, ".jax_cache")}


@pytest.mark.slow
def test_chip_smoke_rehearses_on_cpu():
    """The smoke's own control flow (two children, the server stopped,
    every response checked) at a toy size; the plain invocation would
    refuse this machine."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu"], capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": last["device"]["count"]}}
