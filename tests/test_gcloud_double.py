"""GkeTpuPlatform against an offline gcloud CLI double (the one
provider that touches real TPUs had no offline test of its gcloud
contract).

The double is a real executable placed first on PATH and run through the
provider's DEFAULT subprocess path — argv parsing, exit codes, and the
describe/create/delete statefulness are exercised exactly as against the
real CLI. State lives in a JSON file so create -> describe -> delete
round-trips like a project does.
"""

import json
import os
import stat

import pytest

from kubeflow_tpu.tpctl.apply import Coordinator, GkeTpuPlatform
from kubeflow_tpu.tpctl.tpudef import TpuDef

FAKE_GCLOUD = r'''#!/usr/bin/env python3
"""Stateful gcloud double: container node-pools {describe,create,delete}.

State: $GCLOUD_STATE json {"pools": {name: {...flags}}}. Also appends
every argv to $GCLOUD_STATE.log for contract assertions.
"""
import json, os, sys

state_path = os.environ["GCLOUD_STATE"]
with open(state_path + ".log", "a") as f:
    f.write(json.dumps(sys.argv[1:]) + "\n")
try:
    with open(state_path) as f:
        state = json.load(f)
except FileNotFoundError:
    state = {"pools": {}}
args = sys.argv[1:]
if args[:3] != ["container", "node-pools", args[3] if len(args) > 3 else ""][:3] \
        and args[:2] != ["container", "node-pools"]:
    print("unsupported gcloud surface: " + " ".join(args), file=sys.stderr)
    sys.exit(2)
verb, name = args[2], args[3]
flags = {a.split("=", 1)[0]: (a.split("=", 1)[1] if "=" in a else True)
         for a in args[4:]}
for req in ("--project", "--zone", "--cluster"):
    if req not in flags:
        print(f"missing required flag {req}", file=sys.stderr)
        sys.exit(2)
if verb == "describe":
    if os.environ.get("GCLOUD_FAIL_AUTH"):
        print("ERROR: (gcloud.container.node-pools.describe) "
              "invalid authentication credentials", file=sys.stderr)
        sys.exit(1)
    if name in state["pools"]:
        flags = state["pools"][name]
        labels = dict(kv.split("=", 1) for kv in
                      flags.get("--node-labels", "").split(",") if kv)
        print(json.dumps({
            "name": name,
            "config": {"machineType": flags.get("--machine-type"),
                       "labels": labels},
            "initialNodeCount": int(flags.get("--num-nodes", "1")),
        }))
        sys.exit(0)
    print(f"Not found: projects/x/zones/y/clusters/z/nodePools/{name}",
          file=sys.stderr)
    sys.exit(1)
if verb == "create":
    if name in state["pools"]:
        print(f"Already exists: {name}", file=sys.stderr)
        sys.exit(1)
    if "--machine-type" not in flags or "--num-nodes" not in flags:
        print("create requires --machine-type and --num-nodes",
              file=sys.stderr)
        sys.exit(2)
    state["pools"][name] = flags
elif verb == "delete":
    if "--quiet" not in flags:
        print("delete prompts without --quiet", file=sys.stderr)
        sys.exit(2)
    if name not in state["pools"]:
        print(f"Not found: {name}", file=sys.stderr)
        sys.exit(1)
    del state["pools"][name]
else:
    print(f"unsupported verb {verb}", file=sys.stderr)
    sys.exit(2)
with open(state_path, "w") as f:
    json.dump(state, f)
'''


@pytest.fixture()
def gcloud_env(tmp_path, monkeypatch):
    binpath = tmp_path / "bin"
    binpath.mkdir()
    exe = binpath / "gcloud"
    exe.write_text(FAKE_GCLOUD)
    exe.chmod(exe.stat().st_mode | stat.S_IEXEC)
    state = tmp_path / "state.json"
    monkeypatch.setenv("PATH", f"{binpath}:{os.environ['PATH']}")
    monkeypatch.setenv("GCLOUD_STATE", str(state))
    return state


def _pools(state):
    if not state.exists():
        return {}
    return json.loads(state.read_text())["pools"]


def _calls(state):
    logp = state.with_suffix(".json.log")
    if not logp.exists():
        return []
    return [json.loads(ln) for ln in logp.read_text().splitlines()]


CFG = dict(name="kf", platform="gke-tpu", project="proj-1", zone="us-east5-b",
           accelerator="tpu-v5-lite-podslice", topology="2x4")


def test_apply_creates_pool_through_real_subprocess(gcloud_env):
    cfg = TpuDef(**CFG)
    p = GkeTpuPlatform()
    p.apply(cfg)
    pools = _pools(gcloud_env)
    assert "kf-tpu" in pools
    flags = pools["kf-tpu"]
    assert flags["--machine-type"] == "ct5lp-hightpu-4t"
    assert flags["--num-nodes"] == "2"  # 2x4 = 8 chips / 4 per host
    assert flags["--tpu-topology"] == "2x4"  # multi-host wiring
    assert "gke-tpu-accelerator=tpu-v5-lite-podslice" in flags["--node-labels"]


def test_apply_is_idempotent_via_describe(gcloud_env):
    cfg = TpuDef(**CFG)
    p = GkeTpuPlatform()
    p.apply(cfg)
    p.apply(cfg)  # must NOT attempt a second create (gcloud would fail)
    creates = [c for c in _calls(gcloud_env) if c[2] == "create"]
    assert len(creates) == 1


def test_single_host_pool_has_no_tpu_topology_flag(gcloud_env):
    cfg = TpuDef(**{**CFG, "topology": "2x2"})  # 4 chips = one host
    GkeTpuPlatform().apply(cfg)
    flags = _pools(gcloud_env)["kf-tpu"]
    assert flags["--num-nodes"] == "1"
    assert "--tpu-topology" not in flags


def test_delete_roundtrip_and_double_delete_tolerated(gcloud_env):
    cfg = TpuDef(**CFG)
    p = GkeTpuPlatform()
    p.apply(cfg)
    p.delete(cfg)
    assert _pools(gcloud_env) == {}
    p.delete(cfg)  # second delete: describe says gone -> no-op, no error
    deletes = [c for c in _calls(gcloud_env) if c[2] == "delete"]
    assert len(deletes) == 1


def test_coordinator_end_to_end_with_gke_platform(gcloud_env):
    """The full tpctl apply path: platform provisioning through the
    double + manifests into the fake cluster, then teardown."""
    from kubeflow_tpu.control.k8s.fake import FakeCluster

    cluster = FakeCluster()
    cfg = TpuDef(**{**CFG, "applications": ("crds",)})
    coord = Coordinator(cluster)
    out = coord.apply(cfg)
    assert any(c["type"] == "TpuDefAvailable" and c["status"] == "True"
               for c in out["status"]["conditions"])
    assert "kf-tpu" in _pools(gcloud_env)
    coord.delete(cfg)
    assert _pools(gcloud_env) == {}


def test_auth_failure_never_reads_as_pool_gone(gcloud_env, monkeypatch):
    """Expired credentials during teardown must raise, not silently skip
    the delete of billing hardware."""
    cfg = TpuDef(**CFG)
    p = GkeTpuPlatform()
    p.apply(cfg)
    monkeypatch.setenv("GCLOUD_FAIL_AUTH", "1")
    with pytest.raises(RuntimeError, match="describe failed"):
        p.delete(cfg)
    monkeypatch.delenv("GCLOUD_FAIL_AUTH")
    assert "kf-tpu" in _pools(gcloud_env)  # still there, still visible


def test_spec_drift_fails_instead_of_fake_success(gcloud_env):
    """Re-applying a TpuDef whose topology changed must NOT report
    Available over a stale pool the workload can never schedule on."""
    p = GkeTpuPlatform()
    p.apply(TpuDef(**CFG))  # 2x4 -> 2 hosts
    with pytest.raises(RuntimeError, match="different shape"):
        p.apply(TpuDef(**{**CFG, "topology": "4x4"}))
    # unchanged spec still idempotent
    p.apply(TpuDef(**CFG))


def test_unknown_accelerator_is_loud(gcloud_env):
    with pytest.raises(ValueError, match="unknown TPU accelerator"):
        GkeTpuPlatform().apply(TpuDef(**{**CFG,
                                         "accelerator": "tpu-v5p-podslice"}))


def test_create_failure_surfaces_gcloud_stderr(gcloud_env, monkeypatch):
    """The operator must see gcloud's reason (quota, permissions) in the
    raised error, not a bare 'exit status 1'."""
    cfg = TpuDef(**CFG)
    p = GkeTpuPlatform()
    monkeypatch.setattr(
        GkeTpuPlatform, "commands",
        lambda self, c: [["gcloud", "container", "node-pools", "create",
                          "kf-tpu", "--project=p", "--zone=z",
                          "--cluster=c"]])
    with pytest.raises(RuntimeError, match="machine-type"):
        p.apply(cfg)
