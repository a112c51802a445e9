"""In-pod launcher — the TPU-native replacement for tf-cnn's launcher.py.

Reference contract (tf-controller-examples/tf-cnn/launcher.py):
  - decode TF_CONFIG into --job_name/--ps_hosts/--worker_hosts/--task_index
    (:68-80), exec the payload (:31), then *sleep forever* on success so
    the operator's restartPolicy doesn't rerun it (:90-93).

This launcher:
  - decodes JAXJOB_* env (parallel/dist.py) and joins the jax.distributed
    cluster, with a TCP readiness gate on the coordinator instead of
    sleep-based ordering;
  - with --wait-devices PLATFORM, waits for devices of that platform to
    be visible (the libtpu analogue of the openmpi sidecar's
    /proc/driver/nvidia/version poll, controller.py:73-90) and exits
    EX_UNAVAILABLE (69) if none appear — CPU devices never stand in
    for an accelerator;
  - runs either a built-in trainer (--config JSON/YAML → TrainConfig) or a
    user command;
  - exits 0 on success, 1 on failure, and EX_TEMPFAIL (75) when a
    SIGTERM preemption notice made the trainer checkpoint and leave
    early — the JAXJob controller reads 75 as "gang-restart me, resume
    from the checkpoint", not as a crash. No sleep loop in the pod.

Usage:
    python -m kubeflow_tpu.runtime.launcher --config cfg.yaml
    python -m kubeflow_tpu.runtime.launcher -- python my_train.py --flag
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import subprocess
import sys
import time

from kubeflow_tpu.obs import trace as obs_trace

log = logging.getLogger("kubeflow_tpu.launcher")


# sysexits.h "service unavailable": the platform asked for is not there
EX_UNAVAILABLE = 69


def wait_for_devices(platform: str, timeout_s: float = 300.0) -> int:
    """Block until jax sees devices of `platform` (e.g. "tpu": libtpu
    ready). Devices of another platform never satisfy the wait — in
    particular not the CPU devices jax.devices() falls back to when no
    chip is found. Raises TimeoutError carrying JAX's own reason."""
    import jax

    deadline = time.monotonic() + timeout_s
    while True:
        try:
            devs = jax.devices(platform)
            log.info("devices ready: %d x %s (%s)", len(devs),
                     devs[0].device_kind, platform)
            return len(devs)
        except RuntimeError as e:  # backend absent or failed to initialize
            why = str(e).splitlines()[0]
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"no {platform} devices after {timeout_s:g}s: {why}")
        time.sleep(2.0)


def load_config(path: str) -> dict:
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        from kubeflow_tpu.utils import yaml_lite

        return yaml_lite.loads(text)


def run_builtin_trainer(cfg_dict: dict) -> int:
    from kubeflow_tpu.runtime import metrics as rt_metrics
    from kubeflow_tpu.runtime.preemption import EX_TEMPFAIL, PreemptionNotice
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    metrics_port = int(os.environ.get("JAXRT_METRICS_PORT", "9100"))
    try:
        rt_metrics.serve_metrics(metrics_port)
    except OSError:
        log.warning("metrics port %d busy; metrics endpoint disabled", metrics_port)
    # The worker span: child of the job root (TRACEPARENT env, stamped
    # by the JAXJob controller) — trainer/step spans nest inside it, so
    # one trace runs from "JAXJob created" to "step done".
    from kubeflow_tpu.parallel import dist as D

    try:
        with obs_trace.TRACER.span(
                "worker", process=os.environ.get(D.ENV_PID, ""),
                job=os.environ.get(D.ENV_NAME, "")):
            cfg = TrainConfig.from_dict(cfg_dict)
            # SIGTERM (pod eviction / TPU maintenance) => checkpoint +
            # EX_TEMPFAIL so the JAXJob controller gang-restarts and resumes.
            notice = PreemptionNotice().install()
            world_file = os.environ.get(D.ENV_WORLD_FILE)
            if world_file:
                # elastic job: the controller projects its world stamp
                # into this file (downward API); the coordinator resizes
                # the training world in place on shrink/grow instead of
                # dying with the gang (docs/elastic.md)
                import socket

                from kubeflow_tpu.runtime.elastic import (
                    BATCH_PRESERVE, ElasticCoordinator, file_world_source,
                )

                coord = ElasticCoordinator(
                    file_world_source(world_file),
                    my_name=os.environ.get("HOSTNAME")
                    or socket.gethostname(),
                    notice=notice,
                    batch_policy=os.environ.get(D.ENV_BATCH_POLICY,
                                                BATCH_PRESERVE))
                _, summary = coord.run(
                    cfg, full_world=int(
                        os.environ.get(D.ENV_NPROC, "1")))
            else:
                trainer = Trainer(cfg)
                _, summary = trainer.fit(stop=notice)
    finally:
        _dump_trace()
    print(json.dumps({"summary": summary}), flush=True)
    return EX_TEMPFAIL if summary.get("preempted") else 0


def _dump_trace() -> None:
    """Persist this process's spans (KFTPU_TRACE_FILE=<path>.jsonl);
    tools/trace2perfetto.py turns the dump into a Perfetto timeline."""
    path = os.environ.get("KFTPU_TRACE_FILE")
    if not path:
        return
    try:
        obs_trace.write_jsonl(path, obs_trace.COLLECTOR.spans())
    except OSError as e:
        log.warning("could not write trace dump %s: %s", path, e)


def run_user_command(argv: list[str]) -> int:
    """Exec the user payload, streaming output (launcher.py:31
    run_and_stream analogue, minus the sleep-forever)."""
    log.info("exec: %s", " ".join(argv))
    proc = subprocess.Popen(argv, stdout=sys.stdout, stderr=sys.stderr)
    return proc.wait()


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    user_cmd: list[str] = []
    if "--" in argv:
        i = argv.index("--")
        argv, user_cmd = argv[:i], argv[i + 1 :]

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", help="TrainConfig JSON/YAML for the built-in trainer")
    p.add_argument("--wait-devices", metavar="PLATFORM",
                   help="block until devices of this platform (e.g. tpu) are "
                        "visible before starting; exit 69 if none appear "
                        "within --device-timeout")
    p.add_argument("--device-timeout", type=float, default=300.0)
    args = p.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    from kubeflow_tpu.parallel import backends as B
    from kubeflow_tpu.parallel import dist as D
    from kubeflow_tpu.utils import compile_cache

    log.info("compile cache: %s", compile_cache.configure())

    log.info("collectives backend: %s", B.get_backend().name)

    # Adopt the job's trace context before any spans open: the JAXJob
    # controller stamped TRACEPARENT into the pod env, and attaching it
    # here parents every worker-side span on the job's root span.
    ctx = obs_trace.context_from_env()
    if ctx is not None:
        obs_trace.TRACER.attach(ctx)

    world_file = os.environ.get(D.ENV_WORLD_FILE)
    if world_file and args.config:
        # Elastic built-in-trainer job: the pod env describes the FULL
        # gang, but the live membership is whatever the controller
        # stamped into the world file — under partial admission (or a
        # grow-back replacement joining a shrunken world) they
        # disagree, and a global initialize at the env size would block
        # for peers that were never admitted until it times out. Leave
        # the first world formation to the ElasticCoordinator
        # (wait_for_membership + form_world), which forms from the
        # stamp and retries when the stamp moves mid-join. Only the
        # --config path wires a coordinator: a user command keeps the
        # eager env formation below (its payload owns its own world,
        # and gets no elastic resize — docs/elastic.md).
        log.info("elastic world file %s set: deferring world formation "
                 "to the elastic coordinator", world_file)
    else:
        if world_file:
            log.warning("%s is set but a user command is being run: "
                        "elastic resize only applies to the built-in "
                        "trainer (--config); forming the world from the "
                        "gang env", D.ENV_WORLD_FILE)
        cfg = D.initialize_from_env()
        log.info("process %d/%d (job=%s)", cfg.process_id, cfg.num_processes, cfg.job_name or "-")

    if args.wait_devices:
        try:
            wait_for_devices(args.wait_devices, args.device_timeout)
        except TimeoutError as e:
            log.error("%s", e)
            return EX_UNAVAILABLE

    if args.config:
        # On-demand xprof capture server (JAXRT_PROFILER_PORT) so
        # tensorboard "Capture profile" works against the live pod. Only
        # on the built-in-trainer path: user commands run in a subprocess
        # (the process doing the JAX work), which inherits the env and
        # starts its own server.
        from kubeflow_tpu.runtime.profiler import start_server_from_env

        start_server_from_env()
        return run_builtin_trainer(load_config(args.config))
    if user_cmd:
        return run_user_command(user_cmd)
    p.error("need --config or a user command after --")
    return 2


if __name__ == "__main__":
    sys.exit(main())
