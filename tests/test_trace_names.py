"""Names the benchmark reads in the device trace are a contract: the
readers in benchmarks/metrics/*.json find XLA modules and the flash
kernels' custom calls by regular expressions that no program PR may
edit. A rename must fail here, on the CPU, and not as a metric that
turns to null on the chip."""

import glob
import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REGEX_ARGS = ("module", "single", "fused", "ops")
# what the device trace calls the four step programs
MODULES = {"jit__tick", "jit__step_fused", "jit__paged_prefill_install",
           "jit_train_step"}


def patterns():
    out = []
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmarks", "metrics", "*.json"))):
        with open(path) as f:
            args = json.load(f).get("args", {})
        out += [pytest.param(args[k], id=f"{os.path.basename(path)[:-5]}:{k}")
                for k in REGEX_ARGS if k in args]
    return out


def module_name(jitted, *args) -> str:
    """The XLA module's name as the trace has it: `jit_<function>`."""
    import jax

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args)
    return re.search(r"module @(\w+)", jitted.lower(*shapes).as_text()).group(1)


@pytest.fixture(scope="module")
def program_names():
    """Every name the program gives that a reader may look for: the step
    programs' module names, and a flash custom call as the TPU compiler
    writes it, `%<nested jit's name>.N = <shape> custom-call(`."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.ops.attention import local_attention
    from kubeflow_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer
    from kubeflow_tpu.serving.continuous import SlotDecoder

    model = get_model("transformer-test", vocab_size=64, max_seq_len=24,
                      kv_pages=25, kv_page_size=4)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    dec = SlotDecoder(model, variables, slots=3, prompt_len=8,
                      max_new_tokens=6)
    try:
        table = jnp.asarray(dec.alloc.table)
        names = {
            module_name(dec._step, dec._params, dec.state, table),
            module_name(dec._step_fused, dec._params, dec.state, table),
            module_name(dec.step._paged_prefill_install, dec._params, dec.state,
                        jnp.zeros((1, 8), jnp.int32),
                        jnp.zeros((1,), jnp.int32), table[:1],
                        jnp.zeros((1,), jnp.int32), jnp.int32(0),
                        jnp.int32(1)),
        }
    finally:
        dec.close()
    cfg = TrainConfig.from_dict(dict(
        model="transformer-test", task="lm", global_batch=4, seq_len=32,
        vocab_size=256, mesh=MeshSpec(data=1), total_steps=1))
    trainer = Trainer(cfg, mesh=build_mesh(cfg.mesh,
                                           devices=jax.devices()[:1]))
    names.add(module_name(trainer._train_step, trainer.abstract_state,
                          trainer._example_batch()))
    nested = local_attention.__wrapped__.__name__
    names.add(f"%{nested}.7 = bf16[16,8192,128]{{2,1,0:T(8,128)(2,1)}} "
              "custom-call(%bitcast.1, %bitcast.2, %bitcast.3), "
              'custom_call_target="tpu_custom_call"')
    # a block model's step programs keep the module names, and its two
    # kernels are custom calls as the TPU compiler writes them: the
    # grouped expert matmul under XLA's own name for `ragged_dot`, the
    # block attention under the kernel's (tests/test_paged_attention.py
    # compiles it for a v5e and finds that name in the module)
    from kubeflow_tpu.ops.grouped_matmul import KERNEL_NAME as STREAMED_NAME
    from kubeflow_tpu.ops.moe import EXPERT_MATMUL_TRACE_NAME
    from kubeflow_tpu.ops.paged_attention import (BLOCK_KERNEL_NAME,
                                                  KERNEL_NAME)

    blocks = get_model(
        "transformer-test", vocab_size=64, max_seq_len=24, kv_pages=25,
        kv_page_size=4, moe_every=1, n_experts=4, expert_top_k=2,
        moe_d_ff=32, qk_norm=True, gen_block=4, gen_mask_id=63)
    variables = blocks.init(jax.random.PRNGKey(0),
                            np.zeros((1, 1), np.int32), train=False)
    dec = SlotDecoder(blocks, variables, slots=3, prompt_len=8,
                      max_new_tokens=8)
    try:
        table = jnp.asarray(dec.alloc.table)
        names |= {
            "block:" + module_name(dec._step, dec._params, dec.state, table),
            "block:" + module_name(dec._step_fused, dec._params, dec.state,
                                   table),
            "block:" + module_name(
                dec.step._paged_prefill_install, dec._params, dec.state,
                jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
                table[:1], jnp.zeros((1,), jnp.int32), jnp.int32(0),
                jnp.int32(1), (jnp.zeros((4,), jnp.int32), jnp.int32(1))),
        }
    finally:
        dec.close()
    names.add(f"%{EXPERT_MATMUL_TRACE_NAME}-none.1 = bf16[2048,768]"
              "{1,0:T(8,128)(2,1)S(1)} custom-call(%get-tuple-element, "
              '%x.1, %wu.1), custom_call_target="tpu_custom_call"')
    # and under the streamed kernel's, which starts alike
    # (tests/test_paged_attention.py compiles it for a v5e and finds it)
    names.add(f"%{STREAMED_NAME}.2 = bf16[2048,768]{{1,0:T(8,128)(2,1)}} "
              "custom-call(%get-tuple-element.46, %subtract_clamp_fusion, "
              "%pad_add_fusion, %dynamic_slice.0, %x.1, %wu.1), "
              'custom_call_target="tpu_custom_call"')
    for kernel in (KERNEL_NAME, BLOCK_KERNEL_NAME):
        names.add(f"%{kernel}.3 = bf16[64,128,128]{{2,1,0:T(8,128)(2,1)}} "
                  "custom-call(%table, %start, %last, %q, %k, %v), "
                  'custom_call_target="tpu_custom_call"')
    # a latent layer's tick: the kernel over the latent pool
    # (tests/test_paged_attention.py compiles it for a v5e and finds the
    # name); its rung's flash kernels are `%local_attention.N` like any
    from kubeflow_tpu.ops.paged_latent_attention import (
        KERNEL_NAME as LATENT_NAME)

    names.add(f"%{LATENT_NAME}.4 = bf16[64,64,512]{{2,1,0:T(8,128)(2,1)}} "
              "custom-call(%table, %start, %last, %q, %pool), "
              'custom_call_target="tpu_custom_call"')
    return names


def test_the_step_programs_keep_their_module_names(program_names):
    assert MODULES <= program_names
    # a block model's step takes the one-token step's place under its name
    assert {"block:jit__tick", "block:jit__step_fused",
            "block:jit__paged_prefill_install"} <= program_names


@pytest.mark.parametrize("more", [
    {}, dict(moe_every=1, n_experts=4, expert_top_k=2, moe_d_ff=32,
             qk_norm=True, gen_block=4, gen_mask_id=63)],
    ids=["one-token", "block"])
def test_the_prefill_keeps_its_module_name_at_every_rung(more):
    """The prefill runs at the lengths of a ladder (runtime/kvcache.py),
    each compiled when the decoder is built: what the device trace calls
    each of them is what the decoder dispatches, read from the compiled
    programs themselves."""
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    model = get_model("transformer-test", vocab_size=64, max_seq_len=48,
                      kv_pages=25, kv_page_size=4, **more)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    dec = SlotDecoder(model, variables, slots=2, prompt_len=32,
                      max_new_tokens=8)
    try:
        assert sorted(dec._prefill_at) == [8, 16, 24, 32]
        for length, program in dec._prefill_at.items():
            name = re.search(r"HloModule (\w+)", program.as_text()).group(1)
            assert name == "jit__paged_prefill_install", (length, name)
    finally:
        dec.close()


def test_the_kernels_names_are_what_their_definitions_say():
    """The names said at the kernels' definitions (ops/moe.py,
    ops/paged_attention.py) are the ones the metric files look for."""
    from kubeflow_tpu.ops.grouped_matmul import KERNEL_NAME as STREAMED_NAME
    from kubeflow_tpu.ops.moe import EXPERT_MATMUL_TRACE_NAME
    from kubeflow_tpu.ops.paged_attention import (BLOCK_KERNEL_NAME,
                                                  KERNEL_NAME)

    looked_for = {p.values[0] for p in patterns()}
    assert EXPERT_MATMUL_TRACE_NAME in looked_for
    assert BLOCK_KERNEL_NAME in looked_for
    assert (KERNEL_NAME, BLOCK_KERNEL_NAME) == (
        "paged_decode_attention", "paged_block_attention")
    assert STREAMED_NAME == "ragged-dot-streamed"
    from kubeflow_tpu.ops.paged_latent_attention import (
        KERNEL_NAME as LATENT_NAME)

    assert LATENT_NAME == "paged_latent_attention" \
        and LATENT_NAME in looked_for


def test_both_grouped_matmuls_are_found_by_the_experts_roofline(
        program_names):
    """`moe.expert_roofline.*` builds its expression from the file's
    `ops` and the pass's row count (benchmarks/metrics/blockdiff.py): it
    must find XLA's kernel and the streamed one alike, whichever the rule
    (ops/grouped_matmul.py:use_kernel) gave a pass, or it would divide
    all of the needed bytes by a part of the time."""
    from kubeflow_tpu.ops.grouped_matmul import KERNEL_NAME as STREAMED_NAME
    from kubeflow_tpu.ops.moe import EXPERT_MATMUL_TRACE_NAME

    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "moe.expert_roofline.blockdiff.json")) as f:
        ops = json.load(f)["args"]["ops"]
    assert ops == EXPERT_MATMUL_TRACE_NAME
    assert STREAMED_NAME.startswith(ops)
    rx = re.compile(rf"^%?{ops}[\w.\-]* = \w+\[2048,")
    found = sorted(n.split(" = ")[0] for n in program_names if rx.search(n))
    assert found == [f"%{ops}-none.1", f"%{STREAMED_NAME}.2"]


@pytest.mark.parametrize("pattern", patterns())
def test_every_name_a_metric_reads_is_one_the_program_gives(
        pattern, program_names):
    assert any(re.search(pattern, name) for name in program_names), (
        f"{pattern!r} matches none of {sorted(program_names)}: a metric "
        "of BENCHMARK.json would read null")


def test_the_metric_files_do_name_something():
    assert len(patterns()) >= 8


def test_the_flash_custom_calls_are_in_the_dense_paged_prefill():
    """Where the decoder's rule says flash (`_fresh_prefill_rule`), the
    paged prefill of a dense model calls the nested jit whose name the
    flash kernels carry in the device trace, in both of a rung's cases;
    the gather does not."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.ops.attention import local_attention
    from kubeflow_tpu.serving import steps

    model = get_model("transformer-test", vocab_size=64, max_seq_len=264,
                      kv_pages=34, kv_page_size=8, attention_impl="flash")
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    params = {"params": variables["params"]}
    nested = f"@{local_attention.__wrapped__.__name__}"

    def calls(rung, **kw):
        step = steps.TokenStep(model, params, 2, 256, 8, 33, **kw)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
            (params, step.state, jnp.zeros((1, rung), jnp.int32),
             jnp.zeros((1,), jnp.int32), jnp.zeros((1, 33), jnp.int32),
             jnp.zeros((1,), jnp.int32), jnp.int32(0), jnp.int32(1)))
        text = step._paged_prefill_install.lower(*shapes).as_text()
        assert re.search(r"module @(\w+)", text).group(1) \
            == "jit__paged_prefill_install"
        return len(re.findall(rf"call {nested}\w*\(", text))

    layers = model.cfg.n_layers
    assert calls(128) == 0
    assert calls(256, fresh_prefill=True, prefix_hits=True) == layers
    # a rung that may start behind a hit holds both cases
    assert calls(128, fresh_prefill=True, prefix_hits=True) == 2 * layers
    assert calls(128, fresh_prefill=True) == layers


@pytest.mark.parametrize("name", ["sched.prefill_flash_share.chat",
                                  "sched.prefill_flash_share.doc"])
def test_the_flash_share_reads_counters_the_decoder_has(name):
    """The two counters the metric divides are keys of every decoder's
    `stats()`, so the share is a number (0 here: the CPU's attention is
    the reference's and the rule says gather), and None, not an error,
    over a program that lacks them."""
    import jax

    from benchmarks.metrics import kvwalk
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "kvwalk:growth_share"
    model = get_model("transformer-test", vocab_size=64, max_seq_len=24,
                      kv_pages=25, kv_page_size=4)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                      max_new_tokens=4)
    try:
        before = dec.stats()
        dec.submit([1, 2, 3])
        ctx = {"stats0": before, "stats1": dec.stats()}
    finally:
        dec.close()
    assert set(spec["args"].values()) <= set(before)
    assert kvwalk.growth_share(ctx, **spec["args"]) == 0.0
    assert kvwalk.growth_share({"stats0": {"admitted": 0},
                                "stats1": {"admitted": 1}},
                               **spec["args"]) is None
