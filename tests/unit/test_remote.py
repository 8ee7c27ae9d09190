"""Remote backend tests: deploy → subprocess execute → registry
(reference analog: tests/integration/test_flyte_remote.py, with the
LocalBackend subprocess sandbox standing in for the Flyte sandbox)."""

import sys
from pathlib import Path

import numpy as np

import pytest

APPS_DIR = Path(__file__).parent.parent / "apps"


@pytest.fixture
def fixture_model(monkeypatch, tmp_path):
    monkeypatch.setenv("UNIONML_TPU_HOME", str(tmp_path / "backend"))
    sys.path.insert(0, str(APPS_DIR))
    try:
        import sklearn_app

        sklearn_app.model._backend = None  # reset cached backend per test
        sklearn_app.model.remote(project="fixture-project")
        yield sklearn_app.model
    finally:
        sys.path.remove(str(APPS_DIR))


def test_deploy_and_remote_train(fixture_model):
    version = fixture_model.remote_deploy(app_version="v1")
    assert version == "v1"
    dep_dir = fixture_model._remote.deployment_dir("v1")
    assert (dep_dir / "sklearn_app.py").exists()
    assert (dep_dir / ".unionml_manifest.json").exists()

    artifact = fixture_model.remote_train(app_version="v1", hyperparameters={"max_iter": 200}, n=200)
    assert artifact.model_object is not None
    assert artifact.metrics["test"] > 0.8


def test_remote_predict_and_registry(fixture_model):
    fixture_model.remote_deploy(app_version="v1")
    fixture_model.remote_train(app_version="v1", hyperparameters={"max_iter": 200}, n=200)

    versions = fixture_model.remote_list_model_versions()
    assert len(versions) == 1 and versions[0].startswith("train-")

    preds = fixture_model.remote_predict(model_version="latest", n=50)
    assert isinstance(preds, list) and len(preds) == 50

    # predict from raw features
    preds2 = fixture_model.remote_predict(
        features=[{"x1": 5.0, "x2": 5.0}, {"x1": -5.0, "x2": -5.0}]
    )
    assert preds2 == [1.0, 0.0]


def test_patch_deploy(fixture_model):
    """Patch redeploy overlays source (reference: test_flyte_remote.py:131-146)."""
    fixture_model.remote_deploy(app_version="v1")
    version = fixture_model.remote_deploy(app_version="v1", patch=True)
    assert version.startswith("v1-patch")
    assert fixture_model._remote.deployment_dir(version).exists()


def test_failed_execution_surfaces_log(fixture_model):
    fixture_model.remote_deploy(app_version="v1")
    with pytest.raises(RuntimeError, match="FAILED"):
        # bogus reader kwarg -> workflow TypeError inside the runner process
        fixture_model.remote_train(app_version="v1", bogus_kwarg=1)


def test_execute_requires_deployment(fixture_model):
    with pytest.raises(FileNotFoundError):
        fixture_model.remote_train(app_version="never-deployed")


def test_app_version_dirty_tree_guard(tmp_path, monkeypatch):
    import subprocess

    from unionml_tpu.remote import VersionFetchError, get_app_version

    repo = tmp_path / "repo"
    repo.mkdir()
    subprocess.run(["git", "init", "-q"], cwd=repo, check=True)
    subprocess.run(["git", "config", "user.email", "t@t"], cwd=repo, check=True)
    subprocess.run(["git", "config", "user.name", "t"], cwd=repo, check=True)
    (repo / "f.txt").write_text("hello")
    subprocess.run(["git", "add", "."], cwd=repo, check=True)
    subprocess.run(["git", "commit", "-q", "-m", "init"], cwd=repo, check=True)

    version = get_app_version(cwd=str(repo))
    assert len(version) == 7

    (repo / "f.txt").write_text("dirty")
    with pytest.raises(VersionFetchError, match="uncommitted"):
        get_app_version(cwd=str(repo))
    assert get_app_version(allow_uncommitted=True, cwd=str(repo)).endswith("-dirty")


# ---------------------------------------------------------------------------
# TPUVMBackend with a faked SSH/scp transport (reference analog:
# tests/integration/test_flyte_remote.py:33-57 — a local stand-in instead
# of real cluster hosts). The transport primitives (_ssh/_run_ssh/_scp_*)
# are replaced with local bash/cp so env wiring, per-host logs, failure
# aggregation, and the no-shared-FS fetch path all run for real.
# ---------------------------------------------------------------------------

import os
import subprocess
import threading

REPO_ROOT = Path(__file__).parent.parent.parent


def _make_tpuvm_backend(tmp_path, hosts, **kwargs):
    from unionml_tpu.remote import TPUVMBackend

    kwargs.setdefault("provision", False)
    return TPUVMBackend(
        hosts=hosts,
        project="fixture-project",
        root=str(tmp_path / "backend"),
        workdir=str(tmp_path / "vm_work"),
        **kwargs,
    )


def _fake_transport(monkeypatch, backend, fail_hosts=(), capture=None, stub=False):
    """Local-subprocess stand-ins for the SSH/scp primitives.

    ``stub=True`` records remote commands without executing them (for
    wiring/provisioning assertions); otherwise commands run locally via
    bash, so the real runner executes in the pushed workdir.
    """

    # every fake "host" shares this one file system, and deploy() provisions
    # hosts concurrently: one host's `rm -rf <workdir>` must not run inside
    # another's `cp -r` into the same directory (real hosts have their own)
    shared_fs = threading.Lock()

    def fake_run_ssh(host, command):
        if capture is not None:
            capture.append(("run_ssh", host, command))
        if stub and "pip install" in command:
            return subprocess.CompletedProcess([], 0, "", "")
        if "docker pull" in command:
            # remote docker isn't available in the fake environment in
            # either mode; the capture records the pull for assertions
            return subprocess.CompletedProcess([], 0, "", "")
        with shared_fs:
            return subprocess.run(["bash", "-c", command], capture_output=True, text=True)

    def fake_scp_to(host, src, dst):
        if capture is not None:
            capture.append(("scp_to", host, src, dst))
        # the fake "remote" shares this FS, so a registry stage can target
        # the very dir it comes from — a no-op copy, not an error
        if Path(src.rstrip("/.")).resolve() == Path(dst).resolve():
            return
        with shared_fs:
            subprocess.run(["bash", "-c", f"mkdir -p {dst} && cp -r {src} {dst}"], check=True)

    def fake_scp_from(host, src, dst):
        if capture is not None:
            capture.append(("scp_from", host, src, dst))
        subprocess.run(["bash", "-c", f"mkdir -p {dst} && cp -r {src} {dst}"], check=True)

    def fake_ssh(host, command, **popen_kwargs):
        if capture is not None:
            capture.append(("ssh", host, command))
        if stub:
            return subprocess.Popen(["true"], **popen_kwargs)
        if host in fail_hosts:
            return subprocess.Popen(
                ["bash", "-c", "echo 'fake host crash' >&2; exit 3"], **popen_kwargs
            )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(REPO_ROOT), str(APPS_DIR), env.get("PYTHONPATH", "")]
        )
        return subprocess.Popen(["bash", "-c", command], env=env, **popen_kwargs)

    monkeypatch.setattr(backend, "_run_ssh", fake_run_ssh)
    monkeypatch.setattr(backend, "_scp_to", fake_scp_to)
    monkeypatch.setattr(backend, "_scp_from", fake_scp_from)
    monkeypatch.setattr(backend, "_ssh", fake_ssh)
    return backend


@pytest.fixture
def tpuvm_model(monkeypatch, tmp_path):
    monkeypatch.setenv("UNIONML_TPU_HOME", str(tmp_path / "backend"))
    sys.path.insert(0, str(APPS_DIR))
    try:
        import sklearn_app

        sklearn_app.model._backend = None
        sklearn_app.model.remote(project="fixture-project")
        yield sklearn_app.model, tmp_path
    finally:
        sys.path.remove(str(APPS_DIR))


def test_tpuvm_multihost_env_wiring(tpuvm_model, monkeypatch):
    """Every host gets the jax.distributed coordinator env (host 0 is the
    coordinator) and its own runner log; processes are tracked for wait()."""
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA", "hostB"])
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture, stub=True)
    model._backend = backend

    backend.deploy(model, app_version="v1")
    record = backend.execute(model, workflow="train", app_version="v1",
                             inputs={}, wait=False)
    launched = backend._procs[record.execution_id]
    try:
        cmds = {e[1]: e[2] for e in capture if e[0] == "ssh"}
        assert "JAX_COORDINATOR_ADDRESS=hostA:8476" in cmds["hostA"]
        assert "JAX_NUM_PROCESSES=2" in cmds["hostA"]
        assert "JAX_PROCESS_ID=0" in cmds["hostA"]
        assert "JAX_PROCESS_ID=1" in cmds["hostB"]
        assert len(launched["procs"]) == 2
        for i in range(2):
            assert (Path(record.exec_dir) / f"runner.host{i}.log").exists()
    finally:
        for _, proc, log in launched["procs"]:
            proc.wait(timeout=30)
            log.close()
        backend._procs.pop(record.execution_id, None)


def test_tpuvm_per_host_failure_propagates(tpuvm_model, monkeypatch):
    """A crashed host fails the execution with that host's rc + log tail
    (round-1 gap: _launch fired SSH processes and never looked back)."""
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA", "hostB"])
    _fake_transport(monkeypatch, backend, fail_hosts={"hostB"})
    model._backend = backend

    backend.deploy(model, app_version="v1")
    with pytest.raises(RuntimeError, match=r"host 1 \(hostB\): rc=3"):
        backend.execute(model, workflow="train", app_version="v1",
                        inputs={}, wait=True)
    # the record was marked FAILED for later inspectors — and the host
    # died WITHOUT reporting (simulated crash rc=3), so the failure is
    # classified as a preemption: eligible for execute(max_restarts=)
    from unionml_tpu.remote import ExecutionRecord

    execs = list((Path(str(tmp_path / "backend")) / "executions" /
                  "fixture-project").iterdir())
    assert len(execs) == 1
    rec = ExecutionRecord.load(execs[0])
    assert rec.status == "FAILED"
    assert rec.failure_kind == "preempted"


def test_tpuvm_single_host_end_to_end_without_shared_fs(tpuvm_model, monkeypatch):
    """Full lifecycle over the faked transport with shared_fs=False: deploy
    push -> runner executes in the per-version workdir -> inputs staged out,
    host-0 outputs fetched back -> artifact loads. Single host launches
    without any jax.distributed env."""
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA"], shared_fs=False)
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture)
    model._backend = backend

    model.remote_deploy(app_version="v1")
    artifact = model.remote_train(app_version="v1",
                                  hyperparameters={"max_iter": 200}, n=200)
    assert artifact.model_object is not None
    assert artifact.metrics["test"] > 0.8
    (cmd,) = [e[2] for e in capture if e[0] == "ssh"]
    assert "JAX_COORDINATOR_ADDRESS" not in cmd  # single host: no dist init
    assert "_exec" in cmd  # runner pointed at the staged exec dir
    assert any(e[0] == "scp_from" for e in capture)  # outputs fetched back

    # predict resolves the trained model on the host: without a shared FS
    # the backend must stage the train execution into the host's registry
    preds = model.remote_predict(
        app_version="v1",
        features=[{"x1": 5.0, "x2": 5.0}, {"x1": -5.0, "x2": -5.0}],
    )
    assert preds == [1.0, 0.0]


def test_tpuvm_provisioning_installs_on_every_host(tpuvm_model, monkeypatch):
    """Full deploys push the environment bundle and pip-install it per host;
    patch deploys skip provisioning (fast-registration parity)."""
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA", "hostB"], provision=True)
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture, stub=True)
    model._backend = backend

    def fake_bundle(dest):
        env_dir = Path(dest) / "_env"
        env_dir.mkdir(parents=True, exist_ok=True)
        (env_dir / "unionml_tpu-0.1.0-py3-none-any.whl").write_bytes(b"wheel")
        (env_dir / "requirements.lock").write_text("jax==0.0.test\n")
        return env_dir

    import unionml_tpu.remote.packaging as packaging

    monkeypatch.setattr(packaging, "build_environment_bundle", fake_bundle)

    backend.deploy(model, app_version="v1")
    pip_cmds = [(e[1], e[2]) for e in capture
                if e[0] == "run_ssh" and "pip install" in e[2]]
    assert {h for h, _ in pip_cmds} == {"hostA", "hostB"}
    assert all("requirements.lock" in c and ".whl" in c for _, c in pip_cmds)

    capture.clear()
    backend.deploy(model, app_version="v1-patch123", patch=True)
    assert not [e for e in capture
                if e[0] == "run_ssh" and "pip install" in e[2]]


def test_environment_bundle_builds_offline(tmp_path):
    """The real wheel build + pinned lock (the docker_build_push analog)."""
    from unionml_tpu.remote import build_environment_bundle

    env_dir = build_environment_bundle(tmp_path / "dep")
    wheels = list(env_dir.glob("unionml_tpu-*.whl"))
    assert len(wheels) == 1
    lock = (env_dir / "requirements.lock").read_text()
    assert "jax==" in lock and "flax==" in lock and "optax==" in lock


def test_tpuvm_registry_staging_rewrites_exec_dir(tpuvm_model, monkeypatch):
    """The record staged to a no-shared-FS host must carry the HOST-side
    exec_dir, not the deployer-local one — the runner's fetch_outputs
    follows record.exec_dir, which doesn't exist on a separate FS."""
    import json as _json

    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA"], shared_fs=False)
    _fake_transport(monkeypatch, backend)
    model._backend = backend

    model.remote_deploy(app_version="v1")
    model.remote_train(app_version="v1", hyperparameters={"max_iter": 200}, n=200)

    staged = {}
    orig_scp = backend._scp_to

    def spy_scp(host, src, dst):
        if "/executions/" in dst:
            rec = _json.loads(
                (Path(src.rstrip(".").rstrip("/")) / "record.json").read_text()
            )
            staged["exec_dir"] = rec["exec_dir"]
            staged["dst"] = dst
        orig_scp(host, src, dst)

    monkeypatch.setattr(backend, "_scp_to", spy_scp)
    preds = model.remote_predict(
        app_version="v1",
        features=[{"x1": 5.0, "x2": 5.0}, {"x1": -5.0, "x2": -5.0}],
    )
    assert preds == [1.0, 0.0]
    assert staged, "registry staging never happened"
    assert staged["exec_dir"] == staged["dst"]


def test_remote_train_with_jax_train_state_artifact(monkeypatch, tmp_path):
    """TrainState model objects cross the execution boundary: they are not
    picklable (optax closures), so the runner encodes them as the app's
    saver bytes and remote_load/_load_model_artifact decode them back
    (remote/artifacts.py). Covers remote_train AND remote_predict."""
    monkeypatch.setenv("UNIONML_TPU_HOME", str(tmp_path / "backend"))
    sys.path.insert(0, str(APPS_DIR))
    try:
        import flax_app

        flax_app.model._backend = None
        flax_app.model.remote(project="flax-fixture")
        flax_app.model.remote_deploy(app_version="v1")
        artifact = flax_app.model.remote_train(
            app_version="v1", hyperparameters={"learning_rate": 1e-2}, n=64
        )
        import jax

        assert jax.tree_util.tree_leaves(artifact.model_object.params)
        assert artifact.metrics["test"] >= 0.8

        preds = flax_app.model.remote_predict(
            features=np.ones((4, 8), dtype=np.float32)
        )
        assert preds == [1, 1, 1, 1]
    finally:
        sys.path.remove(str(APPS_DIR))


def test_tpuvm_wait_without_launch_rejected_when_no_shared_fs(tpuvm_model):
    """wait() from a process that did not launch the execution only sees the
    record turn terminal when the launcher's scp lands it (shared_fs=False);
    a timeout must name that cause, not raise a bare TimeoutError."""
    from unionml_tpu.remote.backend import ExecutionRecord

    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA"], shared_fs=False)
    exec_dir = tmp_path / "orphan-exec"
    exec_dir.mkdir()
    record = ExecutionRecord(
        execution_id="orphan", project="fixture-project",
        workflow="train", app_version="v1", exec_dir=str(exec_dir),
    )
    record.save()
    with pytest.raises(TimeoutError, match="shared_fs"):
        backend.wait(record, timeout=1.0)


def test_dump_outputs_names_non_model_offender(fixture_model):
    """An unpicklable key other than model_object must be named in the
    error (chained from the original) instead of failing the saver-encoded
    retry with a second traceback masking the cause."""
    import io

    from unionml_tpu.remote.artifacts import dump_outputs

    outputs = {
        "model_object": {"w": 1.0},
        "hyperparameters": {},
        "metrics": {"callback": lambda x: x},  # unpicklable, not the model
    }
    with pytest.raises(RuntimeError, match="metrics") as err:
        dump_outputs(fixture_model, outputs, io.BytesIO())
    assert err.value.__cause__ is not None  # original pickling error chained


def _fake_docker(monkeypatch, backend, capture, *, fail_on=None):
    """Local docker stand-in: records build/push/pull; `docker run ...`
    launched over SSH is rewritten to execute the inner runner command
    directly, so the containerized launch path runs for real."""

    def fake_run_docker(args):
        capture.append(("docker",) + tuple(args[:2]))
        if fail_on and args[0] == fail_on:
            return subprocess.CompletedProcess([], 1, "", f"fake {fail_on} failure")
        return subprocess.CompletedProcess([], 0, "", "")

    monkeypatch.setattr(backend, "_run_docker", fake_run_docker)
    return backend


def test_tpuvm_image_deploy_builds_pushes_and_pulls(tpuvm_model, monkeypatch):
    """Image mode: full deploy = docker build + push + per-host pull, NO
    pip provisioning; patch deploy skips all image work."""
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(
        tmp_path, ["hostA", "hostB"], provision=True, image="reg.example/app"
    )
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture, stub=True)
    _fake_docker(monkeypatch, backend, capture)

    backend.deploy(model, app_version="v1")
    assert ("docker", "build", "-t") in capture
    assert ("docker", "push", "reg.example/app:v1") in capture
    pulls = [(e[1], e[2]) for e in capture if e[0] == "run_ssh" and "docker pull" in e[2]]
    assert {h for h, _ in pulls} == {"hostA", "hostB"}
    assert all("reg.example/app:v1" in c for _, c in pulls)
    # image supersedes pip provisioning
    assert not [e for e in capture if e[0] == "run_ssh" and "pip install" in e[2]]

    capture.clear()
    backend.deploy(model, app_version="v1-patch123", patch=True)
    assert not [e for e in capture if e[0] == "docker"]
    assert not [e for e in capture if e[0] == "run_ssh" and "docker pull" in e[2]]


def test_tpuvm_image_deploy_failure_surfaces(tpuvm_model, monkeypatch):
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA"], image="reg.example/app")
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture, stub=True)
    _fake_docker(monkeypatch, backend, capture, fail_on="push")
    with pytest.raises(RuntimeError, match="docker push failed"):
        backend.deploy(model, app_version="v1")


def test_tpuvm_image_execution_runs_in_container(tpuvm_model, monkeypatch):
    """The launch command wraps the runner in `docker run` with the
    workdir/registry mounts and env flags; executing it (with the docker
    prefix stripped by the fake transport) completes the full train
    lifecycle — proving the in-container command is the real runner
    invocation."""
    import re

    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(
        tmp_path, ["hostA"], shared_fs=False, image="reg.example/app",
        image_push=False,
    )
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture)
    _fake_docker(monkeypatch, backend, capture)

    real_ssh = backend._ssh

    def docker_exec_ssh(host, command, **popen_kwargs):
        if command.startswith("docker run"):
            m = re.search(r"reg\.example/app:\S+ (python -m unionml_tpu\.remote\.runner .*)$", command)
            assert m, command
            assert f"-v {backend.root}:{backend.root}" in command
            assert "-e UNIONML_TPU_HOME=" in command and "--network host" in command
            # single host: no jax.distributed env
            assert "JAX_COORDINATOR_ADDRESS" not in command
            envs = dict(
                kv.split("=", 1)
                for kv in re.findall(r"-e ([A-Z_]+=\S+)", command)
            )
            inner = m.group(1)
            env = dict(os.environ)
            env.update(envs)
            env["PYTHONPATH"] = os.pathsep.join(
                [str(REPO_ROOT), str(APPS_DIR), env.get("PYTHONPATH", "")]
            )
            wd = re.search(r"-w (\S+)", command).group(1)
            return subprocess.Popen(["bash", "-c", inner], cwd=wd, env=env, **popen_kwargs)
        return real_ssh(host, command, **popen_kwargs)

    monkeypatch.setattr(backend, "_ssh", docker_exec_ssh)
    model._backend = backend
    model.remote_deploy(app_version="v1")
    artifact = model.remote_train(app_version="v1",
                                  hyperparameters={"max_iter": 200}, n=200)
    assert artifact.metrics["test"] > 0.8
    assert any(e[0] == "docker" and e[1] == "build" for e in capture)


# ---------------------------------------------------------------------------
# Stage.resources are consumed at launch (reference: unionml/defaults.py:5
# sizes the task container; here the launcher derives the runner env)


def test_resources_env_derivation():
    from unionml_tpu.defaults import Resources, cpu_count, resources_env

    host_only = Resources(cpu="2", mem="1Gi", chips=0)
    env = resources_env(host_only)
    assert env["JAX_PLATFORMS"] == "cpu"  # never grab the accelerator
    assert env["OMP_NUM_THREADS"] == "2"
    device = Resources(cpu="500m", mem="8Gi", chips=1)
    env = resources_env(device)
    assert "JAX_PLATFORMS" not in env     # the accelerator stays visible
    assert env["OMP_NUM_THREADS"] == "1"  # fractional cpu rounds up to 1
    assert cpu_count(Resources(cpu="nonsense")) == 1


def test_workflow_resources_take_stage_maxima():
    from unionml_tpu.defaults import Resources
    from unionml_tpu.remote.backend import _mem_bytes, _workflow_resources
    from unionml_tpu.stage import Workflow, stage_from_fn

    wf = Workflow("wf")
    reader = stage_from_fn(
        lambda: [], name="reader", owner=None,
        resources=Resources(cpu="1", mem="512Mi", chips=0),
    )
    trainer = stage_from_fn(
        lambda: None, name="trainer", owner=None,
        resources=Resources(cpu="4", mem="8Gi", chips=1, accelerator="tpu-v5e"),
    )
    wf.add_node(reader, {})
    wf.add_node(trainer, {})
    env = _workflow_resources(wf)
    assert env.cpu == "4" and env.chips == 1 and env.mem == "8Gi"
    assert env.accelerator == "tpu-v5e"
    assert _mem_bytes("512Mi") < _mem_bytes("1Gi") < _mem_bytes("2G")


def test_manifest_env_backcompat_and_chips0():
    from unionml_tpu.remote.backend import _manifest_env

    # pre-round-4 manifests carry no resources: no overrides
    assert _manifest_env({"app": "x:y"}, "train") == {}
    manifest = {
        "resources": {
            "prep": {"cpu": "2", "mem": "1Gi", "chips": 0, "accelerator": None},
            "train": {"cpu": "4", "mem": "8Gi", "chips": 1, "accelerator": "tpu-v5e"},
        }
    }
    assert _manifest_env(manifest, "prep")["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in _manifest_env(manifest, "train")
    assert _manifest_env(manifest, "unknown") == {}


def test_local_backend_applies_resources_env(fixture_model, monkeypatch):
    """The launched runner's environment carries the derived resource env.
    The sklearn fixture is a HOST-ONLY model family, so its stages default
    to chips=0 (Resources docstring promise): the runner env pins
    JAX_PLATFORMS=cpu and caps threadpools at the host default."""
    import subprocess as sp

    import unionml_tpu.remote.backend as backend_mod

    model = fixture_model
    backend = model._remote
    backend.deploy(model, app_version="rv1")
    manifest_path = backend.deployment_dir("rv1") / ".unionml_manifest.json"
    assert "resources" in manifest_path.read_text()

    captured = {}
    real_popen = sp.Popen

    def capture_popen(cmd, **kwargs):
        captured["env"] = kwargs.get("env", {})
        return real_popen(["true"], stdout=kwargs.get("stdout"),
                          stderr=kwargs.get("stderr"))

    monkeypatch.setattr(backend_mod.subprocess, "Popen", capture_popen)
    record = backend.execute(
        model, workflow=model.train_workflow_name, app_version="rv1",
        inputs={}, wait=False,
    )
    assert record is not None
    assert captured["env"]["OMP_NUM_THREADS"] == "1"
    # host-only workflow (chips=0): the launcher pins JAX_PLATFORMS=cpu so
    # a data-prep/sklearn run never grabs the accelerator a co-tenant
    # serving process is using
    assert captured["env"].get("JAX_PLATFORMS") == "cpu"

    # device workflow (chips=1): redeploy with explicit device resources —
    # the launcher must apply the thread caps but NOT pin the platform
    # (whatever JAX_PLATFORMS the ambient env carries passes through).
    # monkeypatch-scoped: the sklearn_app module is SHARED across tests,
    # so unrestored mutations leak into later fixtures (caught by the
    # tpuvm resources test failing only in full-suite order)
    from unionml_tpu.defaults import DEFAULT_DEVICE_RESOURCES

    monkeypatch.setitem(
        model._train_task_kwargs, "resources", DEFAULT_DEVICE_RESOURCES
    )
    monkeypatch.setattr(model, "_train_task", None)  # regenerate stage
    backend.deploy(model, app_version="rv2")
    captured.clear()
    record = backend.execute(
        model, workflow=model.train_workflow_name, app_version="rv2",
        inputs={}, wait=False,
    )
    assert record is not None
    assert captured["env"]["OMP_NUM_THREADS"] == "4"
    import os as _os

    assert captured["env"].get("JAX_PLATFORMS") == _os.environ.get(
        "JAX_PLATFORMS"
    )


def test_tpuvm_resources_env_in_ssh_command(tpuvm_model, monkeypatch):
    model, tmp_path = tpuvm_model
    backend = _make_tpuvm_backend(tmp_path, ["hostA"])
    capture = []
    _fake_transport(monkeypatch, backend, capture=capture, stub=True)
    model._backend = backend
    backend.deploy(model, app_version="v1")
    record = backend.execute(model, workflow="train", app_version="v1",
                             inputs={}, wait=False)
    launched = backend._procs[record.execution_id]
    try:
        cmds = {e[1]: e[2] for e in capture if e[0] == "ssh"}
        # sklearn app = host-only family: chips=0 defaults flow into the
        # SSH launch line (thread cap + platform pin)
        assert "OMP_NUM_THREADS=1" in cmds["hostA"]
        assert "JAX_PLATFORMS=cpu" in cmds["hostA"]
    finally:
        for _, proc, log in launched["procs"]:
            proc.wait(timeout=30)
            log.close()
        backend._procs.pop(record.execution_id, None)


def test_elastic_train_step_survives_preemption(monkeypatch, tmp_path):
    """SURVEY §5.3 e2e: a train_step registered with checkpoint_dir is
    preemption-safe through the remote lifecycle. The runner is
    HARD-KILLED (os._exit — no cleanup, no terminal status) mid-run;
    LocalBackend.wait detects the dead pid, execute(max_restarts=1)
    relaunches the same execution, the elastic trainer resumes from the
    newest checkpoint, and the final state is BIT-IDENTICAL to an
    uninterrupted run."""
    import numpy as np

    monkeypatch.setenv("UNIONML_TPU_HOME", str(tmp_path / "backend"))
    sys.path.insert(0, str(APPS_DIR))
    try:
        import elastic_app

        model = elastic_app.model
        model._backend = None
        model.remote(project="elastic-project")
        backend = model._remote

        # 48 train rows / batch 8 = 6 steps/epoch x 4 epochs = 24 steps;
        # checkpoints at 2,4,...; the bomb kills the runner at step 5
        monkeypatch.setenv("UNIONML_TEST_DIE_AT", "5")
        trainer_kwargs = {"num_epochs": 4, "batch_size": 8, "seed": 0}
        backend.deploy(model, app_version="e1")
        record = backend.execute(
            model, workflow="train", app_version="e1",
            inputs={"trainer_kwargs": trainer_kwargs},
            wait=True, max_restarts=1,
        )
        assert record.status == "SUCCEEDED"
        log = (Path(record.exec_dir) / "runner.log").read_text()
        assert "died without reporting" in log   # the kill really happened
        assert "resuming from step" in log       # ...and the relaunch RESUMED
        interrupted = backend.fetch_outputs(record)["model_object"]

        # control: fresh deployment (fresh relative checkpoint dir), no bomb
        monkeypatch.delenv("UNIONML_TEST_DIE_AT")
        backend.deploy(model, app_version="e2")
        record2 = backend.execute(
            model, workflow="train", app_version="e2",
            inputs={"trainer_kwargs": trainer_kwargs}, wait=True,
        )
        control = backend.fetch_outputs(record2)["model_object"]
        np.testing.assert_array_equal(
            np.asarray(interrupted["w"]), np.asarray(control["w"])
        )
        np.testing.assert_array_equal(
            np.asarray(interrupted["b"]), np.asarray(control["b"])
        )
    finally:
        sys.path.remove(str(APPS_DIR))


def test_max_restarts_skips_deterministic_failures(fixture_model, monkeypatch):
    """An app-REPORTED failure (reproducible crash) must surface
    immediately — max_restarts only retries preemptions (runner died
    without reporting), or every buggy run would retrain N times."""
    model = fixture_model
    backend = model._remote
    backend.deploy(model, app_version="df1")
    launches = []
    real_launch = backend._launch

    def counting_launch(*a, **k):
        launches.append(1)
        return real_launch(*a, **k)

    monkeypatch.setattr(backend, "_launch", counting_launch)
    with pytest.raises(RuntimeError, match="FAILED"):
        backend.execute(
            model, workflow="train", app_version="df1",
            inputs={"bogus_kwarg": 1},   # deterministic TypeError in-app
            wait=True, max_restarts=3,
        )
    assert len(launches) == 1, "deterministic failure was relaunched"


def test_pinned_requirements_toml_fallback_parser():
    """The Python-3.10 textual fallback must survive extras brackets
    inside specs and comments — a ']' only terminates the array
    OUTSIDE quotes (silently dropping deps would ship a broken env)."""
    from unionml_tpu.remote.packaging import _parse_dependencies_toml

    tricky = "\n".join([
        "[build-system]",
        'requires = ["setuptools"]',
        "[project]",
        'name = "x"',
        "dependencies = [",
        '    "jax[tpu]>=0.4.30",  # extras bracket inside the spec',
        "    'flax>=0.8',",
        '    "numpy>=1.24",',
        "]",
        "[project.optional-dependencies]",
        'dev = ["pytest"]',
    ])
    assert _parse_dependencies_toml(tricky) == [
        "jax[tpu]>=0.4.30", "flax>=0.8", "numpy>=1.24",
    ]
    assert _parse_dependencies_toml(
        '[project]\ndependencies = ["a[x]>=1", "b>=2"]\n'
    ) == ["a[x]>=1", "b>=2"]
    with pytest.raises(KeyError):
        _parse_dependencies_toml("[project]\nname='x'\n")
