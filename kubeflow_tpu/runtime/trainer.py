"""The training loop: pjit-compiled steps over a named mesh.

This replaces the reference's entire distributed-training data plane. In
the reference, each step is: workers compute grads on GPU, push/pull every
variable to a parameter server over gRPC (launcher.py:74-80) or
ring-allreduce via MPI+NCCL (openmpi-controller). Here the step is ONE
compiled XLA program: forward, backward, gradient reduction (psum /
reduce-scatter over ICI), and optimizer update all fused by GSPMD — zero
host involvement per step.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_DCN,
    AXIS_FSDP,
    AXIS_PIPELINE,
    BATCH_AXES,
    MeshSpec,
    build_mesh,
    batch_sharding,
    mesh_summary,
)
from kubeflow_tpu.parallel.shardings import infer_shardings, unbox
from kubeflow_tpu.runtime import metrics as rt_metrics
from kubeflow_tpu.runtime.data import synthetic_images, synthetic_tokens, shard_batch

log = logging.getLogger("kubeflow_tpu.trainer")


@dataclasses.dataclass
class TrainConfig:
    """Declarative training config — the payload section of a JAXJob spec.

    Mirrors the knob surface of the reference's tf-cnn job generator
    (create_job_specs.py:101-121: model, batch_size, data_format,
    num_batches) plus the TPU-native axes the reference lacked.
    """

    model: str = "resnet50"
    model_kwargs: dict = dataclasses.field(default_factory=dict)
    task: str = "classification"  # classification | lm
    global_batch: int = 32        # reference default: --batch_size=32 per worker
    image_size: int = 224
    num_classes: int = 1000
    seq_len: int = 1024
    vocab_size: int = 32000
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    optimizer: str = "sgdm"       # sgdm | adamw
    learning_rate: float = 0.1
    weight_decay: float = 1e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    remat: bool = False
    # "full" recomputes everything; "dots" keeps matmul outputs and
    # recomputes only elementwise; "mlp" (LM only) saves everything
    # except the d_ff-wide MLP tensors — most of the memory win at the
    # smallest recompute tax. For task=lm these select the model's
    # per-block remat; elsewhere the whole forward is checkpointed.
    remat_policy: str = "full"
    pp_microbatches: int = 4        # pipeline microbatches when mesh.pipe > 1
    aux_loss_weight: float = 0.01   # weight on sowed aux losses (MoE balance)
    # LM only: compute the head + cross-entropy in this many sequence
    # chunks (ops/xent.py) so the [B, L, V] logits tensor never
    # materializes — frees GBs of activation memory at large batch.
    # 0/1 = classic full-logits loss.
    xent_chunks: int = 0
    # Split each step's batch into this many microbatches, lax.scan the
    # forward+backward over them and apply ONE averaged optimizer update:
    # activation memory scales with the microbatch while the optimizer
    # sees the full global batch. 0/1 = single-shot step.
    grad_accum_steps: int = 0
    seed: int = 0
    log_every: int = 20
    # orbax checkpoint/resume (SURVEY.md §5): async saves + resume-from-
    # latest on gang restart. checkpoint_every=0 => save only at the end.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0
    checkpoint_keep: int = 3
    resume: bool = True
    # Real data: glob of KFRecord token shards (runtime/records.py). When
    # unset, synthetic batches (the tf_cnn_benchmarks default) are used.
    data_path: str | None = None
    shuffle_buffer: int = 0
    # LM shards written by write_packed_token_shard: batches gain
    # segment_ids (flash masks cross-document attention) and -1 targets
    # at padding/boundaries (ignored by the loss).
    packed_data: bool = False
    # Periodic held-out evaluation (the reference's estimator
    # train_and_evaluate pattern): every eval_every train steps run
    # eval_steps batches from eval_data_path (same shard format as
    # data_path) and log the averaged metrics (+ perplexity for LM).
    # When eval_data_path is unset, eval falls back to the TRAINING
    # source reshuffled at a shifted seed — a smoke eval, not held-out;
    # point eval_data_path at real validation shards for generalization
    # numbers. 0 = no eval.
    eval_every: int = 0
    eval_steps: int = 8
    eval_data_path: str | None = None
    # Flash-attention kernel tiles, so a swept operating point is
    # reproducible from the config alone (0 = kernel default /
    # KFTPU_FLASH_BLOCK_Q/K env). Forwarded into the LM model's config —
    # explicit plumbing, no process-global state.
    flash_block_q: int = 0
    flash_block_k: int = 0
    # xprof trace window (runtime/profiler.py): capture steps
    # [profile_start_step, profile_start_step + profile_steps).
    profile_dir: str | None = None
    profile_start_step: int = 2
    profile_steps: int = 3

    @classmethod
    def from_dict(cls, d: dict) -> "TrainConfig":
        d = dict(d)
        if "mesh" in d and not isinstance(d["mesh"], MeshSpec):
            d["mesh"] = MeshSpec.from_dict(d["mesh"])
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown TrainConfig keys {sorted(unknown)}")
        return cls(**d)


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any            # {} for stateless models
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.total_steps, cfg.warmup_steps + 1),
    )
    if cfg.optimizer == "sgdm":
        return optax.chain(
            optax.add_decayed_weights(cfg.weight_decay),
            optax.sgd(sched, momentum=0.9, nesterov=True),
        )
    if cfg.optimizer == "adamw":
        return optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=cfg.weight_decay)
    if cfg.optimizer == "adafactor":
        # The TPU-native memory-light optimizer (T5 lineage): second moment
        # factored into row+col statistics, so optimizer state is ~0 bytes
        # per param instead of 8 — what lets llama-1b-class models train on
        # a single 16 GB v5e chip.
        return optax.adafactor(
            learning_rate=sched,
            multiply_by_parameter_scale=True,
            weight_decay_rate=cfg.weight_decay or None,
        )
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def _batch_xy(cfg: TrainConfig, batch: dict):
    """Input/target selection per task. seq_classification = BERT-style
    fine-tuning: token sequences in, one label per sequence out."""
    if cfg.task == "classification":
        return batch["image"], batch["label"]
    if cfg.task == "seq_classification":
        return batch["tokens"], batch["label"]
    return batch["tokens"], batch["targets"]


def _masked_accuracy(pred: jax.Array, labels: jax.Array) -> jax.Array:
    """argmax hit-rate over valid (non-negative) labels only."""
    valid = labels >= 0
    return (jnp.sum((pred == labels) & valid)
            / jnp.maximum(jnp.sum(valid), 1))


def _xent_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Integer-label cross entropy in f32, shared by classification and LM
    (LM logits are [B, L, V], labels [B, L] — mean over all positions).
    Negative labels are ignored (packed-batch padding / document
    boundaries, records.token_batches segmented mode)."""
    valid = labels >= 0
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), jnp.maximum(labels, 0))
    return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)


class Trainer:
    """Builds mesh + model + sharded step functions from a TrainConfig."""

    def __init__(self, cfg: TrainConfig, mesh=None):
        self.cfg = cfg
        if mesh is None:
            # default mesh construction rides the selected collectives
            # backend (parallel/backends.py): ONE placement code path,
            # parameterized by the mesh-axes→levels map. The default
            # (single) backend with the default map is build_mesh
            # byte-for-byte; loopback/tpu lay DCN-level axes over the
            # slice boundary.
            from kubeflow_tpu.parallel import backends as B

            mesh = B.get_backend().mesh(cfg.mesh)
        self.mesh = mesh
        log.info("trainer mesh: %s", mesh_summary(self.mesh))
        # LM models remat per-block inside the model (see _model_kwargs);
        # everything else gets whole-forward jax.checkpoint in _build.
        self._model_self_remat = cfg.remat and cfg.task == "lm"
        self.model = get_model(cfg.model, **self._model_kwargs())
        self.tx = make_optimizer(cfg)
        self._build()

    def _model_kwargs(self) -> dict:
        kw = dict(self.cfg.model_kwargs)
        # LM models (TransformerLM family) handle remat themselves with
        # per-block nn.remat: the backward pass then holds ONE block's
        # intermediates at a time, with only the b·s·d residual stream
        # saved per layer. Wrapping the whole forward in jax.checkpoint
        # (the non-LM fallback in _build) saves almost nothing — the
        # backward recompute still materializes every layer's activations
        # at once, which is why gpt-760m-class models OOMed under it.
        if self._model_self_remat:
            kw.setdefault("remat", True)
            kw.setdefault("remat_policy", self.cfg.remat_policy)
        if self.cfg.task == "lm":
            if self.cfg.flash_block_q:
                kw.setdefault("flash_block_q", self.cfg.flash_block_q)
            if self.cfg.flash_block_k:
                kw.setdefault("flash_block_k", self.cfg.flash_block_k)
            # same guard as num_classes below: synthetic targets draw
            # from cfg.vocab_size, and a model head with a different
            # registry default would see out-of-range labels -> NaN loss
            kw.setdefault("vocab_size", self.cfg.vocab_size)
        if self.cfg.task in ("classification", "seq_classification"):
            if kw.get("num_classes", self.cfg.num_classes) != self.cfg.num_classes:
                # the data generator draws labels from cfg.num_classes; a
                # diverging model head silently yields NaN loss
                raise ValueError(
                    f"model_kwargs.num_classes={kw['num_classes']} conflicts "
                    f"with num_classes={self.cfg.num_classes}; set the "
                    "top-level num_classes only")
            kw.setdefault("num_classes", self.cfg.num_classes)
        pipe = self.mesh.shape.get(AXIS_PIPELINE, 1)
        if pipe > 1:
            if self.cfg.task != "lm":
                raise ValueError("pipeline parallelism (mesh.pipe > 1) is only "
                                 "supported for transformer LM tasks")
            if self.cfg.global_batch % self.cfg.pp_microbatches:
                raise ValueError(
                    f"global_batch {self.cfg.global_batch} not divisible by "
                    f"pp_microbatches {self.cfg.pp_microbatches}"
                )
            kw.setdefault("pipeline_stages", pipe)
            kw.setdefault("pp_microbatches", self.cfg.pp_microbatches)
        return kw

    def _example_batch(self) -> dict:
        cfg = self.cfg
        if cfg.task == "classification":
            return {
                "image": jnp.zeros((cfg.global_batch, cfg.image_size, cfg.image_size, 3), jnp.float32),
                "label": jnp.zeros((cfg.global_batch,), jnp.int32),
            }
        if cfg.task == "seq_classification":
            return {
                "tokens": jnp.zeros((cfg.global_batch, cfg.seq_len), jnp.int32),
                "label": jnp.zeros((cfg.global_batch,), jnp.int32),
            }
        return {
            "tokens": jnp.zeros((cfg.global_batch, cfg.seq_len), jnp.int32),
            "targets": jnp.zeros((cfg.global_batch, cfg.seq_len), jnp.int32),
        }

    def data_iter(self, data_path: str | None = None,
                  seed: int | None = None) -> Iterator[dict]:
        cfg = self.cfg
        data_path = data_path if data_path is not None else cfg.data_path
        seed = seed if seed is not None else cfg.seed
        if data_path:
            import glob as _glob

            paths = sorted(_glob.glob(data_path))
            if not paths:
                raise FileNotFoundError(f"no shards match {data_path!r}")
            if cfg.task == "classification":
                from kubeflow_tpu.runtime.records import image_batches

                return image_batches(paths, cfg.global_batch, cfg.image_size,
                                     shuffle_buffer=cfg.shuffle_buffer,
                                     seed=seed, loop=True)
            from kubeflow_tpu.runtime.records import token_batches

            return token_batches(paths, cfg.global_batch, cfg.seq_len,
                                 shuffle_buffer=cfg.shuffle_buffer,
                                 seed=seed, loop=True,
                                 segmented=cfg.packed_data)
        if cfg.task == "classification":
            return synthetic_images(cfg.global_batch, cfg.image_size, cfg.num_classes, seed)
        if cfg.task == "seq_classification":
            from kubeflow_tpu.runtime.data import synthetic_token_classes

            return synthetic_token_classes(cfg.global_batch, cfg.seq_len,
                                           cfg.vocab_size, cfg.num_classes,
                                           seed)
        return synthetic_tokens(cfg.global_batch, cfg.seq_len, cfg.vocab_size, seed)

    def eval_data_iter(self) -> Iterator[dict]:
        """Held-out batches: eval_data_path shards when given, else the
        training source at a shifted seed (different shuffle/draw)."""
        cfg = self.cfg
        return self.data_iter(data_path=cfg.eval_data_path or cfg.data_path,
                              seed=cfg.seed + 1)

    def _device_iter(self, it: Iterator[dict]) -> Iterator[dict]:
        """Device-put each distinct host batch once. The synthetic
        iterators yield the *same* numpy arrays every step; without this
        cache every step re-uploads the full batch host->device inside the
        metered window (deflating MFU). Keyed by object identity so real
        pipelines that produce fresh arrays still upload each batch."""
        sharding = next(iter(jax.tree.leaves(self.batch_shardings)))
        last_key, last_val = None, None
        for b in it:
            key = tuple(id(a) for a in jax.tree.leaves(b))
            if key != last_key:
                last_val = shard_batch(b, sharding)
                last_key = key
            yield last_val

    # ---- build jitted fns ------------------------------------------------

    def _dp_size(self) -> int:
        """Ways the batch axis is sharded (dcn * data * fsdp * expert)."""
        n = 1
        for a in BATCH_AXES:
            n *= self.mesh.shape[a]
        return n

    def _init_fn(self, rng):
        batch = self._example_batch()
        x = batch["image"] if self.cfg.task == "classification" else batch["tokens"]
        # Init with one row per data-parallel group: parameter shapes don't
        # depend on batch, but the init forward must still satisfy the
        # batch-axis sharding (ring attention shard_maps over it).
        variables = self.model.init(rng, x[:self._dp_size()], train=True)
        return variables

    def _build(self) -> None:
        cfg, mesh = self.cfg, self.mesh
        rng = jax.random.PRNGKey(cfg.seed)

        abstract = jax.eval_shape(self._init_fn, rng)
        self.var_shardings = infer_shardings(abstract, mesh)
        self.n_params = sum(
            leaf.size for leaf in jax.tree.leaves(unbox(abstract)["params"])
        )
        # Strip Partitioned boxes from both the abstract tree and shardings
        # consumers; real arrays are unboxed after init.
        # infer_shardings maps each Partitioned box to a single NamedSharding
        # leaf, so the shardings tree lines up with the *unboxed* variables.
        self._init_jit = jax.jit(
            lambda r: unbox(self._init_fn(r)), out_shardings=self.var_shardings
        )
        self.batch_shardings = jax.tree.map(
            lambda _: batch_sharding(mesh), self._example_batch()
        )

        # The train state's layout, decided once: init_state builds to it,
        # the train step is pinned to return it, and an ahead-of-time
        # compile (tools/aot_tpu.py) traces with it. The optimizer state
        # is built from zeros, so nothing ties it to the params' devices:
        # left to itself jit puts ALL of it on the first device, and a
        # model that needs fsdp to fit dies there. Moments shaped like
        # their param take that param's sharding; the rest (counts,
        # factored statistics) is small and replicated.
        def sds(tree, shardings):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                tree, shardings)

        rep = NamedSharding(mesh, P())
        variables = sds(unbox(abstract), self.var_shardings)
        opt_abstract = jax.eval_shape(self.tx.init, variables["params"])
        self.abstract_state = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            params=variables["params"],
            batch_stats=variables.get("batch_stats", {}),
            opt_state=sds(opt_abstract, optax.tree_utils.tree_map_params(
                self.tx,
                lambda leaf, p: p.sharding if leaf.shape == p.shape else rep,
                opt_abstract, variables["params"],
                transform_non_params=lambda _: rep)),
            tx=self.tx)
        self.abstract_batch = sds(jax.eval_shape(self._example_batch),
                                  self.batch_shardings)
        self.state_shardings = jax.tree.map(lambda a: a.sharding,
                                            self.abstract_state)

        # Positional-only closure so jax.checkpoint sees pure pytree args
        # (it rejects string kwargs like mutable=[...]). seg is the
        # optional [B, L] sequence-packing ids (LM batches only) — the
        # flash kernel masks cross-document attention from them.
        # "diagnostics" carries per-step observability sows (MoE dispatch
        # fill/drop — ops/moe.py) that must NOT contribute to the loss.
        _MUTABLE = ["batch_stats", "losses", "diagnostics"]

        def forward(variables, x, seg=None):
            kw = {"segment_ids": seg} if seg is not None else {}
            return self.model.apply(
                variables, x, train=True, mutable=_MUTABLE, **kw
            )

        if cfg.remat and not self._model_self_remat:
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            elif cfg.remat_policy == "full":
                policy = jax.checkpoint_policies.nothing_saveable
            else:
                # "mlp" (and anything else) is a per-block LM policy; a
                # silent fallback to full recompute here would look like a
                # mysterious step-time regression instead of a config error
                raise ValueError(
                    f"remat_policy {cfg.remat_policy!r} is not supported for "
                    f"task={cfg.task!r} (whole-forward remat takes dots|full)")
            forward = jax.checkpoint(forward, policy=policy)

        chunked_head = cfg.task == "lm" and cfg.xent_chunks > 1
        if chunked_head:
            from kubeflow_tpu.ops.xent import chunked_lm_xent

            # same operand dtype as LMHead's matmul (bf16 on the standard
            # configs; f32 models stay exact)
            head_dtype = getattr(
                getattr(self.model, "cfg", None), "dtype", jnp.bfloat16)

            def forward_hidden(variables, x, seg=None):
                kw = {"segment_ids": seg} if seg is not None else {}
                return self.model.apply(
                    variables, x, train=True, return_hidden=True,
                    mutable=_MUTABLE, **kw)

            def chunked_loss_acc(params, hidden, y):
                return chunked_lm_xent(
                    hidden, params["lm_head"]["kernel"], y, cfg.xent_chunks,
                    compute_dtype=head_dtype)

        def loss_fn(params, batch_stats, batch):
            variables = {"params": params, **({"batch_stats": batch_stats} if batch_stats else {})}
            x, y = _batch_xy(cfg, batch)
            # optional packed-sequence ids ride in the batch dict (LM only)
            seg = batch.get("segment_ids") if cfg.task == "lm" else None
            if chunked_head:
                # Head + loss chunked over sequence (ops/xent.py): the
                # [B, L, V] logits tensor never materializes; lm_head
                # kernel grads flow through the chunk scan directly.
                hidden, new_vars = forward_hidden(variables, x, seg)
                loss, acc = chunked_loss_acc(params, hidden, y)
            else:
                logits, new_vars = forward(variables, x, seg)
                loss = _xent_loss(logits, y)
                acc = _masked_accuracy(logits.argmax(-1), y)
            # auxiliary losses sowed by modules (e.g. MoE load balancing)
            aux_leaves = jax.tree.leaves(new_vars.get("losses", {}))
            if aux_leaves:
                loss = loss + cfg.aux_loss_weight * sum(a.mean() for a in aux_leaves)
            # valid-position count: the weight grad accumulation must use
            # so packed microbatches with uneven -1 masking still combine
            # into the exact full-batch token-weighted mean
            n_valid = jnp.sum(y >= 0)
            # mean each diagnostics sow into one scalar per name (the
            # sow name is the innermost dict key; sows across layers
            # average), e.g. moe_fill / moe_drop
            from jax.tree_util import tree_flatten_with_path

            sums: dict = {}
            for path, v in tree_flatten_with_path(
                    new_vars.get("diagnostics", {}))[0]:
                name = next((p.key for p in reversed(path)
                             if hasattr(p, "key")), None)
                if name is not None:
                    sums.setdefault(str(name), []).append(v)
            diag = {k: sum(v) / len(v) for k, v in sums.items() if v}
            return loss, (new_vars.get("batch_stats", {}), acc, n_valid, diag)

        accum = max(1, cfg.grad_accum_steps)
        if accum > 1:
            if cfg.global_batch % accum:
                raise ValueError(
                    f"global_batch {cfg.global_batch} not divisible by "
                    f"grad_accum_steps {accum}")
            dp = self._dp_size()
            if (cfg.global_batch // accum) % dp:
                raise ValueError(
                    f"microbatch {cfg.global_batch // accum} not divisible "
                    f"by the {dp}-way batch sharding (dcn*data*fsdp*expert)")
            if (mesh.shape.get(AXIS_PIPELINE, 1) > 1
                    and (cfg.global_batch // accum) % cfg.pp_microbatches):
                raise ValueError(
                    f"microbatch {cfg.global_batch // accum} not divisible "
                    f"by pp_microbatches {cfg.pp_microbatches} (each scanned "
                    "microbatch is re-split by the pipeline)")

        def _microbatches(batch):
            """[B, ...] -> [accum, B/accum, ...] with a STRIDED row split:
            row r lands in microbatch r % accum, so each microbatch draws
            evenly from every device's contiguous batch shard (a block
            split would put whole microbatches on a subset of the mesh).

            The split is device-local under the batch sharding: row
            j*accum+m of a contiguous dp shard maps to row j of the same
            shard in microbatch m. GSPMD cannot see that through
            reshape+swapaxes on its own — without an explicit constraint
            it replicates the stacked tensor and re-partitions it every
            scan iteration ("[SPMD] Involuntary full rematerialization"),
            a per-step full-batch broadcast on real dcn×fsdp jobs."""
            def split(a):
                a = a.reshape(
                    (a.shape[0] // accum, accum) + a.shape[1:]).swapaxes(0, 1)
                spec = P(None, BATCH_AXES, *([None] * (a.ndim - 2)))
                return jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, spec))

            return jax.tree.map(split, batch)

        def _apply_update(state, grads, new_stats, loss, acc, diag=None):
            updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt,
            )
            return new_state, {"loss": loss, "accuracy": acc, **(diag or {})}

        # (the name is read: the device trace has the step as the module
        # `jit_train_step`, and PERF.md and the ledger's breakdown name
        # idle gaps by it; pinned by tests/test_trace_names.py)
        def train_step(state: TrainState, batch):
            (loss, (new_stats, acc, _, diag)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                state.params, state.batch_stats, batch
            )
            return _apply_update(state, grads, new_stats, loss, acc, diag)

        def train_step_accum(state: TrainState, batch):
            # Per-microbatch losses are means over that microbatch's VALID
            # positions; packed batches (-1 targets) can distribute them
            # unevenly, so the combine weights each microbatch by its
            # valid count — making the cross-entropy term == one big
            # batch EXACTLY, not just for uniform masking. Auxiliary
            # losses (MoE balance) are token-weighted too — deliberate:
            # a microbatch whose router saw more real tokens exerts
            # proportionally more balancing pressure.
            def body(carry, microbatch):
                stats, g_sum, loss_sum, acc_sum, n_sum = carry
                # re-pin the batch sharding on the scanned slice: the scan
                # carries only the stacked tensor's sharding, and the
                # sliced view needs the same anchor or the whole forward
                # propagates from an unconstrained operand
                microbatch = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, NamedSharding(
                            mesh, P(BATCH_AXES, *([None] * (a.ndim - 1))))),
                    microbatch)
                (loss, (new_stats, acc, n, diag)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, stats, microbatch)
                w = n.astype(jnp.float32)
                return (new_stats,
                        jax.tree.map(lambda a, g: a + g * w, g_sum, grads),
                        loss_sum + loss * w, acc_sum + acc * w,
                        n_sum + w), (diag, w)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (new_stats, g_sum, loss_sum, acc_sum, n_sum), (diags, ws) = \
                jax.lax.scan(
                    body,
                    (state.batch_stats, zeros, jnp.float32(0.0),
                     jnp.float32(0.0), jnp.float32(0.0)),
                    _microbatches(batch))
            n = jnp.maximum(n_sum, 1.0)
            grads = jax.tree.map(
                lambda g, p: (g / n).astype(p.dtype), g_sum, state.params)
            # diagnostics combine token-weighted, matching loss/acc: with
            # packed batches the microbatch valid-token counts differ, and
            # an unweighted mean of moe_fill/moe_drop would drift from the
            # single-step definition (ADVICE r4).
            diag = jax.tree.map(lambda a: (a * ws).sum() / n, diags)
            return _apply_update(state, grads, new_stats,
                                 loss_sum / n, acc_sum / n, diag)

        # out_shardings: the new state comes back laid out as it went in.
        # Left to the compiler it re-shards what it likes (on a v5e 2x2
        # it spreads adafactor's row statistics over fsdp), step 2 then
        # arrives with other shardings than step 1 — a new cache key —
        # and the whole step compiles a second time.
        self._train_step = jax.jit(
            train_step_accum if accum > 1 else train_step, donate_argnums=(0,),
            out_shardings=(self.state_shardings, None))

        def eval_step(state: TrainState, batch):
            variables = {"params": state.params,
                         **({"batch_stats": state.batch_stats} if state.batch_stats else {})}
            x, y = _batch_xy(cfg, batch)
            seg = batch.get("segment_ids") if cfg.task == "lm" else None
            kw = {"segment_ids": seg} if seg is not None else {}
            if chunked_head:
                # a config that only FITS because training chunks the head
                # must not OOM on its first eval
                hidden = self.model.apply(variables, x, train=False,
                                          return_hidden=True, **kw)
                loss, acc = chunked_loss_acc(state.params, hidden, y)
                return {"loss": loss, "accuracy": acc}
            logits = self.model.apply(variables, x, train=False, **kw)
            return {"loss": _xent_loss(logits, y),
                    "accuracy": _masked_accuracy(logits.argmax(-1), y)}

        self._eval_step = jax.jit(eval_step)

    # ---- public API ------------------------------------------------------

    def init_state(self) -> TrainState:
        from kubeflow_tpu.obs import trace as obs_trace

        rng = jax.random.PRNGKey(self.cfg.seed)
        # model and optimizer init, a part of set-up: traced, compiled
        # and enqueued here (the first step waits for what still runs)
        with obs_trace.TRACER.span("train.init", model=self.cfg.model):
            with self.mesh:
                variables = self._init_jit(rng)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            # the step counter is made on the mesh like the rest of the
            # state: an uncommitted leaf comes back committed from step 1,
            # which is a new jit cache key, and the whole train step
            # compiles twice
            step, opt_state = jax.jit(
                lambda p: (jnp.zeros((), jnp.int32), self.tx.init(p)),
                out_shardings=(self.state_shardings.step,
                               self.state_shardings.opt_state))(params)
        log.info("model %s: %.2fM params", self.cfg.model, self.n_params / 1e6)
        return TrainState(
            step=step,
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            tx=self.tx,
        )

    def train_step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        batch = shard_batch(batch, next(iter(jax.tree.leaves(self.batch_shardings))))
        with self.mesh:
            return self._train_step(state, batch)

    def eval_step(self, state: TrainState, batch: dict) -> dict:
        batch = shard_batch(batch, next(iter(jax.tree.leaves(self.batch_shardings))))
        with self.mesh:
            return self._eval_step(state, batch)

    def _log_placement(self, state: TrainState, batch: dict) -> None:
        """Say where the state and the batch really live. shard_constraint
        is a no-op without an ambient mesh and a sharding that did not
        take is silent, so four chips can quietly do one chip's work; the
        per-device byte counts make that visible in the log."""
        per_dev: dict[int, int] = {}
        for leaf in jax.tree.leaves((state.params, state.opt_state)):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
        x = next(iter(jax.tree.leaves(batch)))
        log.info(
            "placement: params+optimizer MB per device %s; batch %s over %d "
            "devices in shards of %s",
            {d: round(n / 2**20, 1) for d, n in sorted(per_dev.items())},
            tuple(x.shape), len(x.sharding.device_set),
            tuple(x.sharding.shard_shape(x.shape)))

    def flops_per_step(self) -> float:
        """Analytic train-step FLOPs for the MFU meter.

        Convention: multiply and add count separately (2*MACs), matching
        peak_flops' spec-sheet convention — feeding MAC counts (the
        fvcore/"4.1 GFLOPs resnet50" number) into a 2*MAC peak silently
        halves MFU. Train = 3x fwd (dgrad + wgrad each ~ fwd).
        """
        cfg = self.cfg
        if cfg.model.startswith("resnet"):
            from kubeflow_tpu.models.resnet import fwd_flops

            per_image = fwd_flops(
                cfg.model, image_size=cfg.image_size,
                num_classes=cfg.num_classes,
                num_filters=cfg.model_kwargs.get("num_filters", 64),
                stem=cfg.model_kwargs.get("stem", "conv7"))
            return 3.0 * per_image * cfg.global_batch
        if hasattr(self.model, "fwd_flops_per_image"):
            return 3.0 * self.model.fwd_flops_per_image() * cfg.global_batch
        if hasattr(self.model, "flops_per_token"):
            per_token = self.model.flops_per_token(seq_len=cfg.seq_len)
            return per_token * cfg.global_batch * cfg.seq_len
        # fallback: dense 6*N per token
        return 6.0 * self.n_params * cfg.global_batch * cfg.seq_len

    @staticmethod
    def _gang_agreed_stop(local_stop: Callable[[], bool]) -> Callable[[], bool]:
        """Collective agreement on the stop flag. SIGTERM lands on gang
        workers at different instants, but orbax saves of mesh-sharded
        arrays are collective — every process must break at the SAME
        step. Each poll all-gathers the local flag across processes (a
        matched collective, since every worker polls once per step); any
        worker's notice stops the whole gang at that step."""
        from jax.experimental import multihost_utils

        import numpy as np

        def agreed() -> bool:
            flags = multihost_utils.process_allgather(
                np.asarray(bool(local_stop())))
            return bool(np.any(flags))

        return agreed

    def fit(self, steps: int | None = None, state: TrainState | None = None,
            callback: Callable[[int, dict], None] | None = None,
            stop: Callable[[], bool] | None = None) -> tuple[TrainState, dict]:
        """Run the training loop; returns final state + summary metrics.

        `steps` is the global step target: on a gang restart with
        cfg.checkpoint_dir set, training resumes from the latest orbax
        checkpoint and runs only the remaining steps.

        `stop` is polled once per step (e.g. runtime.preemption's
        SIGTERM notice): when it returns True the loop force-saves a
        checkpoint and returns early with summary["preempted"]=True, so
        a gang restart resumes from the interrupted step instead of the
        last periodic save.
        """
        cfg = self.cfg
        steps = steps or cfg.total_steps
        state = state or self.init_state()
        if stop is not None and jax.process_count() > 1:
            stop = self._gang_agreed_stop(stop)

        ckpt = None
        if cfg.checkpoint_dir:
            from kubeflow_tpu.runtime.checkpoint import Checkpointer

            from kubeflow_tpu.parallel import dist as D

            world = D.active_world()
            ckpt = Checkpointer(cfg.checkpoint_dir, keep=cfg.checkpoint_keep,
                                world_size=jax.process_count(),
                                num_slices=world.num_slices if world else 1)
            if cfg.resume:
                restored = ckpt.restore_latest(state)
                if restored is not None:
                    state = restored
                    log.info("resumed from checkpoint at step %d", int(state.step))
        start_step = int(state.step)
        if start_step >= steps:
            # Target already reached (resume landed at/after it): no-op run.
            # Same summary schema as the normal path; executed count is
            # always steps - start_step.
            if ckpt:
                ckpt.close()
            return state, {"steps": steps, "start_step": start_step,
                           "device": rt_metrics.device_info(
                               self.mesh.devices.flat),
                           "first_step_s": None, "step_time_s": None,
                           "examples_per_sec": 0.0, "mfu": None, "final": {}}

        from kubeflow_tpu.obs import trace as obs_trace

        data = None
        device = rt_metrics.device_info(self.mesh.devices.flat)
        # tracer=: each metered step emits a train.step span under the
        # ambient context — linked to the gang-admission span when the
        # launcher attached the pod's TRACEPARENT. Metering starts after
        # the compile step, hence the +1 global-step base.
        meter = rt_metrics.StepMeter(self.flops_per_step(), device["count"], device["kind"],
                                     tracer=obs_trace.TRACER,
                                     step_base=start_step + 1)
        last = {}
        last_saved = -1
        first_dt = float("nan")
        import time as _time

        def maybe_save(gstep: int, st) -> None:
            nonlocal last_saved
            if ckpt and cfg.checkpoint_every and gstep % cfg.checkpoint_every == 0:
                if ckpt.save(gstep, st):
                    last_saved = gstep

        last_eval: dict = {}

        def maybe_eval(gstep: int, st) -> None:
            # train_and_evaluate parity: average eval_steps held-out
            # batches; perplexity for LM (exp of the masked mean NLL).
            # A FRESH iterator per eval scores the same leading window of
            # the eval set every time, so the metric is comparable across
            # steps (a persistent iterator would score disjoint slices).
            # Building it here — inside fit's try — also means a bad
            # eval_data_path still closes the checkpointer on unwind.
            nonlocal last_eval
            if not (cfg.eval_every and gstep % cfg.eval_every == 0):
                return
            eval_iter = iter(self.eval_data_iter())
            import math as _m

            sums: dict = {}
            try:
                for _ in range(max(1, cfg.eval_steps)):
                    m = self.eval_step(st, next(eval_iter))
                    for k, v in m.items():
                        sums[k] = sums.get(k, 0.0) + float(v)
            finally:
                # shard-backed iterators hold a native reader thread
                if hasattr(eval_iter, "close"):
                    eval_iter.close()
            last_eval = {k: v / max(1, cfg.eval_steps) for k, v in sums.items()}
            if cfg.task == "lm":
                last_eval["perplexity"] = _m.exp(min(last_eval["loss"], 30.0))
            # Without eval_data_path this "eval" reads the TRAINING source
            # at a shifted seed — a smoke check, not held-out perplexity
            # (with shuffle_buffer=0 it scores the training shards'
            # leading window verbatim). Mark it so the gauges, the log
            # line, and the summary can't be mistaken for generalization.
            smoke = not cfg.eval_data_path
            last_eval["smoke"] = float(smoke)
            kind = "training-data smoke eval" if smoke else "held-out eval"
            for k, v in last_eval.items():
                rt_metrics.REGISTRY.gauge(f"jaxrt_eval_{k}", v,
                                          f"{kind} {k}")
            log.info("%s @ step %d: %s", kind, gstep,
                     " ".join(f"{k}={v:.4f}" for k, v in sorted(last_eval.items())))

        from kubeflow_tpu.runtime.profiler import TraceWindow

        trace = TraceWindow(cfg.profile_dir, cfg.profile_start_step,
                            cfg.profile_steps)

        # the host's share of a pass: kftpu.train.<phase> annotations in
        # the profiler's trace (data, dispatch, wait, save, eval,
        # callback), and the first three as attributes of the step's span
        def phase(p: str):
            return obs_trace.annotation(
                f"{obs_trace.ANNOTATION_PREFIX}train.{p}")

        def run_step(state, batch, data_s: float):
            """Dispatch a step and wait for its loss; with the seconds
            `next(data)` took, the host split of the step."""
            t0 = _time.perf_counter()
            with phase("dispatch"):
                state, m = self.train_step(state, batch)
            t1 = _time.perf_counter()
            with phase("wait"):
                jax.block_until_ready(m["loss"])
            t2 = _time.perf_counter()
            return state, m, {"data_wait_s": round(data_s, 6),
                              "dispatch_s": round(t1 - t0, 6),
                              "device_wait_s": round(t2 - t1, 6)}

        ok = False
        preempted = False
        # Fit span: nest under the caller's ambient span when one is
        # open (the launcher's "worker" span), else fall back to the
        # pod's TRACEPARENT so a Trainer built outside the launcher
        # still joins the job trace, else start a new root.
        fit_span = obs_trace.TRACER.begin(
            "train.fit",
            parent=obs_trace.TRACER.current() or obs_trace.context_from_env(),
            model=cfg.model, global_batch=cfg.global_batch,
            start_step=start_step, steps=steps)
        try:
            # Data construction inside the try: its failure modes (no
            # shards match the glob, native loader required but missing)
            # must still close the checkpointer on unwind.
            if cfg.data_path:
                # Real data: background host->device prefetch overlaps the
                # upload of batch N+1 with compute of batch N.
                from kubeflow_tpu.runtime.data import Prefetcher

                data = Prefetcher(
                    self.data_iter(),
                    next(iter(jax.tree.leaves(self.batch_shardings))),
                )
            else:
                data = self._device_iter(self.data_iter())
            for i in range(steps - start_step):
                if stop is not None and stop():
                    # preemption notice: persist progress and leave — the
                    # gang restart resumes from exactly this step
                    preempted = True
                    # force=False: if this step already exists on disk
                    # (resume=N then preempted again before N+1), keep it —
                    # force's delete-then-save would open a window where
                    # the only durable checkpoint is gone
                    if ckpt and int(state.step) != last_saved:
                        if ckpt.save(int(state.step), state):
                            last_saved = int(state.step)
                    log.warning("preempted at step %d: checkpoint saved, "
                                "exiting early", int(state.step))
                    break
                trace.step(start_step + i)
                # one pass = one step in the profiler's own step view
                with jax.profiler.StepTraceAnnotation(
                        "train", step_num=start_step + i):
                    t_data = _time.perf_counter()
                    with phase("data"):
                        batch = next(data)
                    data_s = _time.perf_counter() - t_data
                    if i == 0:
                        self._log_placement(state, batch)
                        # Step 0 pays XLA compile; keep it out of the meter
                        # window so step_time/throughput/MFU reflect steady
                        # state.
                        t0 = _time.perf_counter()
                        with obs_trace.TRACER.span(
                                "train.step", step=start_step,
                                compile=True) as sp:
                            state, m, split = run_step(state, batch, data_s)
                            sp.attrs.update(split)
                        first_dt = _time.perf_counter() - t0
                        log.info("first step (incl. compile): %.2fs", first_dt)
                        last = {k: float(v) for k, v in m.items()}
                    else:
                        # the span's extent stays dispatch to
                        # block_until_ready: obs/goodput.py classifies by it
                        meter.start()
                        state, m, split = run_step(state, batch, data_s)
                        meter.stop(**split)
                        if (i + 1) % cfg.log_every == 0 or i == steps - start_step - 1:
                            last = {k: float(v) for k, v in m.items()}
                            rt_metrics.REGISTRY.gauge("jaxrt_step_seconds", meter.step_time,
                                                      "mean step wall time")
                            rt_metrics.REGISTRY.gauge("jaxrt_examples_per_sec",
                                                      meter.throughput(cfg.global_batch),
                                                      "training throughput")
                            if meter.peak:  # a utilization needs a known chip
                                rt_metrics.REGISTRY.gauge("jaxrt_mfu", meter.mfu, "model FLOPs utilization")
                            rt_metrics.REGISTRY.gauge("jaxrt_loss", last["loss"], "training loss")
                            log.info(
                                "step %d loss=%.4f acc=%.3f %.1f ex/s step=%.1fms%s",
                                i + 1, last["loss"], last.get("accuracy", float("nan")),
                                meter.throughput(cfg.global_batch), meter.step_time * 1e3,
                                f" mfu={meter.mfu * 100:.1f}%" if meter.peak else "",
                            )
                    with phase("save"):
                        maybe_save(start_step + i + 1, state)
                    with phase("eval"):
                        maybe_eval(start_step + i + 1, state)
                    if callback:
                        with phase("callback"):
                            callback(i, m)
            ok = True
        finally:
            meter.close()  # a step that raised still exports, as ERROR
            fit_span.attrs["preempted"] = preempted
            if not ok and fit_span.status == "OK":
                fit_span.status = "ERROR"
            obs_trace.TRACER.finish(fit_span)
            trace.stop()
            if hasattr(data, "close"):
                data.close()  # stop the prefetch thread
            if ckpt:
                # Final save only on a completed (not preempted) run: the
                # stop branch already persisted the preempted step, and a
                # force=True save here would reopen the delete-then-save
                # window on the checkpoint it resumed from. Always close so
                # queued async saves finish durably even when unwinding on
                # an exception.
                if ok and not preempted and int(state.step) != last_saved:
                    ckpt.save(int(state.step), state, force=True)
                ckpt.close()
        import math as _math

        if meter.steps == 0 and _math.isfinite(first_dt):
            # single-step run: only the compile step exists to report
            meter._times.append(first_dt)

        def _finite(x: float):
            # summary is json.dumps'ed by the launcher and parsed by
            # controllers; bare NaN is not valid JSON, so a run preempted
            # before any step completed reports null instead
            return x if _math.isfinite(x) else None

        summary = {
            "steps": steps,
            "start_step": start_step,
            "device": device,
            # the first step pays tracing and compilation: set-up, kept
            # apart from the steady step time below
            "first_step_s": _finite(first_dt),
            "step_time_s": _finite(meter.step_time),
            "examples_per_sec": _finite(meter.throughput(cfg.global_batch)),
            "mfu": _finite(meter.mfu),
            "final": {k: _finite(v) for k, v in last.items()},
        }
        if preempted:
            summary["preempted"] = True
        if last_eval:
            summary["eval"] = {k: _finite(v) for k, v in last_eval.items()}
        return state, summary
