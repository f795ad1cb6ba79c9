"""Tests for auxiliary capabilities: resource localization, workflow shim,
in-driver preprocess mode, TB sidecar URL, metrics accumulator."""

import json
import os
import sys
import zipfile
from pathlib import Path

import pytest

from tony_tpu.api import JobStatus
from tony_tpu.client import TonyClient
from tony_tpu.conf import TonyConf
from tony_tpu.integrations import WorkflowJob, props_to_conf
from tony_tpu.integrations.workflow import load_properties
from tony_tpu.metrics import MetricsAccumulator
from tony_tpu.utils import localization as loc

PY = sys.executable


def base_conf(dirs, **extra):
    return TonyConf({
        "tony.staging.dir": dirs["staging"],
        "tony.history.intermediate": dirs["history"] + "/intermediate",
        "tony.am.monitor-interval-ms": 100,
        **extra,
    })


# ------------------------------------------------------------- localization

def test_resource_spec_parsing():
    s = loc.ResourceSpec.parse("/a/b/data.txt#mydata")
    assert s.path == "/a/b/data.txt" and s.alias == "mydata" and not s.archive
    s2 = loc.ResourceSpec.parse("/a/venv.zip::archive")
    assert s2.archive and s2.alias == "venv.zip"
    s3 = loc.ResourceSpec.parse("/a/plain.bin")
    assert s3.alias == "plain.bin"


def test_stage_and_localize_roundtrip(tmp_path):
    src = tmp_path / "data.txt"
    src.write_text("payload")
    zpath = tmp_path / "bundle.zip"
    with zipfile.ZipFile(zpath, "w") as zf:
        zf.writestr("inner/file.txt", "zipped")

    specs = loc.parse_resources([f"{src}#renamed.txt", f"{zpath}#bundle::archive"])
    staged = loc.stage_resources(specs, tmp_path / "staging")
    work = tmp_path / "work"
    loc.localize_resources(staged, work)
    assert (work / "renamed.txt").read_text() == "payload"
    assert (work / "bundle" / "inner" / "file.txt").read_text() == "zipped"


def test_e2e_resource_localization(tmp_job_dirs, tmp_path):
    data = tmp_path / "asset.txt"
    data.write_text("hello-resource")
    conf = base_conf(
        tmp_job_dirs,
        **{"tony.worker.instances": 1,
           "tony.worker.resources": f"{data}#input.txt",
           # cwd of the user process is the task work dir with the resource
           "tony.worker.command": "bash -c 'grep -q hello-resource input.txt'"},
    )
    client = TonyClient(conf, poll_interval_s=0.1)
    client.submit()
    assert client.monitor() == JobStatus.SUCCEEDED


# ----------------------------------------------------------------- workflow

def test_props_to_conf_and_tags():
    conf = props_to_conf(
        {"tony.worker.instances": "3", "unrelated.key": "x",
         "tony.application.name": "wf-job"},
        tags={"flow": "f1", "project": "p1"},
    )
    assert conf["tony.worker.instances"] == 3
    assert "unrelated.key" not in conf
    assert "flow=f1" in conf["tony.application.tags"]


def test_properties_file_roundtrip(tmp_path):
    p = tmp_path / "job.properties"
    p.write_text("# comment\ntony.worker.instances=2\ntony.x.y: value with spaces\n")
    props = load_properties(p)
    assert props["tony.worker.instances"] == "2"
    assert props["tony.x.y"] == "value with spaces"


def test_workflow_job_runs(tmp_job_dirs, fixture_script):
    job = WorkflowJob({
        "tony.staging.dir": tmp_job_dirs["staging"],
        "tony.history.intermediate": tmp_job_dirs["history"] + "/intermediate",
        "tony.worker.instances": "1",
        "tony.worker.command": f"{PY} {fixture_script('exit_0.py')}",
        "tony.am.monitor-interval-ms": "100",
    }, tags={"flow": "test-flow"})
    assert job.run() == 0


# ------------------------------------------------------ preprocess + sidecar

def test_preprocess_runs_in_driver(tmp_job_dirs, tmp_path):
    """enable-preprocess + single task -> no container, driver forks the
    command itself (reference doPreprocessingJob:784-836)."""
    marker = tmp_path / "ran_in_driver"
    conf = base_conf(
        tmp_job_dirs,
        **{"tony.application.enable-preprocess": True,
           "tony.worker.instances": 1,
           "tony.worker.command": f"bash -c 'echo $PPID > {marker}'"},
    )
    client = TonyClient(conf, poll_interval_s=0.1)
    client.submit()
    assert client.monitor() == JobStatus.SUCCEEDED
    assert marker.exists()
    # no executor containers were launched
    assert not (Path(client.job_dir) / "logs" / "worker_0.stderr").exists()


def test_tensorboard_sidecar_registers_url(tmp_job_dirs):
    conf = base_conf(
        tmp_job_dirs,
        **{"tony.worker.instances": 1,
           "tony.worker.command": "bash -c 'sleep 1'",
           "tony.tensorboard.instances": 1,
           "tony.tensorboard.command": "bash -c 'test -n \"$TB_PORT\" && sleep 1'",
           "tony.application.untracked.jobtypes": "tensorboard"},
    )
    client = TonyClient(conf, poll_interval_s=0.1)
    client.submit()
    status = client.monitor()
    assert status == JobStatus.SUCCEEDED
    assert client.final_state.get("tensorboard_url", "").startswith("http://")


# -------------------------------------------------------------------- metrics

def test_tpu_metric_parsing():
    """The libtpu-SDK metric reducer — analogue of the reference's
    TestGpuDeviceInformationParser fixture tests."""
    from tony_tpu.metrics import (
        TPU_DUTY_CYCLE, TPU_HBM_USED, parse_tpu_metric_values,
    )

    assert parse_tpu_metric_values(
        "duty_cycle_pct", ["0.00", "20.00", "40.00", "0.00"]
    ) == {TPU_DUTY_CYCLE: 15.0}
    assert parse_tpu_metric_values(
        "hbm_capacity_usage", ["1073741824", "0"]
    ) == {TPU_HBM_USED: 1073741824 / 1e6}
    # empty list = runtime not serving metrics on this host -> sample nothing
    assert parse_tpu_metric_values("duty_cycle_pct", []) == {}
    with pytest.raises(ValueError):
        parse_tpu_metric_values("unknown_metric", ["1"])
    with pytest.raises(ValueError):
        parse_tpu_metric_values("duty_cycle_pct", ["not-a-number"])


def _fake_live_tpu_jax(monkeypatch, devices):
    """A stand-in jax whose bridge registry shows a live backend holding
    ``devices`` — what the process that owns the chip looks like."""
    import sys
    import types

    fake_jax = types.ModuleType("jax")
    fake_jax.local_devices = lambda: list(devices)
    fake_jax.live_arrays = lambda: []
    fake_jax._src = types.SimpleNamespace(
        xla_bridge=types.SimpleNamespace(_backends={"tpu": object()}))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    return fake_jax


def test_sample_tpu_metrics_with_mocked_sdk(monkeypatch):
    """End-to-end sampler against a mocked libtpu.sdk module tree, in a
    process that owns a (fake) live TPU client."""
    import sys
    import types

    from tony_tpu import metrics as M

    _fake_live_tpu_jax(monkeypatch, [types.SimpleNamespace(
        platform="tpu", memory_stats=lambda: None)])

    class FakeMetric:
        def __init__(self, data):
            self._d = data

        def data(self):
            return self._d

    data = {
        "duty_cycle_pct": ["50.00", "100.00"],
        "hbm_capacity_usage": ["2000000", "3000000"],
    }
    tpumonitoring = types.SimpleNamespace(
        get_metric=lambda name: FakeMetric(data[name]),
        list_supported_metrics=lambda: list(data),
    )
    sdk = types.ModuleType("libtpu.sdk")
    sdk.tpumonitoring = tpumonitoring
    libtpu = types.ModuleType("libtpu")
    libtpu.sdk = sdk
    monkeypatch.setitem(sys.modules, "libtpu", libtpu)
    monkeypatch.setitem(sys.modules, "libtpu.sdk", sdk)

    out = M.sample_tpu_metrics()
    assert out == {M.TPU_DUTY_CYCLE: 75.0, M.TPU_HBM_USED: 5.0}

    # a runtime error on one metric must not lose the other
    def flaky(name):
        if name == "duty_cycle_pct":
            raise RuntimeError("runtime not initialized")
        return FakeMetric(data[name])

    tpumonitoring.get_metric = flaky
    assert M.sample_tpu_metrics() == {M.TPU_HBM_USED: 5.0}


def test_sample_tpu_metrics_never_touches_libtpu_without_a_live_client(
        monkeypatch):
    """A chip belongs to one process. The executor's monitor runs before
    and while its child holds the chip, so a process without an
    initialised TPU client must get {} WITHOUT importing libtpu.sdk —
    loading it takes libtpu's lock ahead of the child, or blocks."""
    import sys

    from tony_tpu import metrics as M

    real_import = __builtins__["__import__"] if isinstance(
        __builtins__, dict) else __builtins__.__import__

    def guard(name, *a, **k):
        assert not name.startswith("libtpu"), "must not load libtpu"
        return real_import(name, *a, **k)

    # jax imported but no backend initialised (the bridge registry is
    # empty), and jax absent altogether: both are non-owners
    import types

    idle = types.ModuleType("jax")
    idle._src = types.SimpleNamespace(
        xla_bridge=types.SimpleNamespace(_backends={}))
    idle.local_devices = lambda: (_ for _ in ()).throw(
        AssertionError("must not initialise a backend"))
    monkeypatch.setattr("builtins.__import__", guard)
    for mod in (idle, None):
        if mod is None:
            monkeypatch.delitem(sys.modules, "jax", raising=False)
        else:
            monkeypatch.setitem(sys.modules, "jax", mod)
        out, reason = M.sample_tpu_metrics(explain=True)
        assert out == {} and "owns the chip" in reason


def test_sample_tpu_metrics_jax_memory_stats_fallback(monkeypatch):
    """When tpumonitoring serves no per-chip HBM data, an
    ALREADY-initialised jax client's memory_stats() fills in live
    occupancy. The fallback must never import jax itself — from the
    executor's monitor that would initialize a second TPU client
    contending with the child for the chip."""
    import sys
    import types

    from tony_tpu import metrics as M

    class FakeDev:
        def __init__(self, bytes_in_use, platform="tpu"):
            self._b = bytes_in_use
            self.platform = platform

        def memory_stats(self):
            if self._b is None:
                return None          # a runtime that serves no stats
            return {"bytes_in_use": self._b,
                    "peak_bytes_in_use": self._b * 2}

    fake_jax = types.ModuleType("jax")
    fake_jax.local_devices = lambda: [FakeDev(4_000_000), FakeDev(8_000_000)]
    # a live backend must be POSITIVELY visible in the bridge registry or
    # the fallback stays out (fail-safe against jax version bumps)
    fake_jax._src = types.SimpleNamespace(
        xla_bridge=types.SimpleNamespace(_backends={"tpu": object()}))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    monkeypatch.delitem(sys.modules, "libtpu", raising=False)
    monkeypatch.delitem(sys.modules, "libtpu.sdk", raising=False)

    out, reason = M.sample_tpu_metrics(explain=True)
    # SUM over chips, like the sdk — plus the peak-bytes watermark gauge
    # (capacity planning's number) where the runtime serves it
    assert out == {M.TPU_HBM_USED: 12.0, M.TPU_HBM_PEAK: 24.0}
    assert reason is None                     # non-empty sample: no excuse

    # a runtime that serves occupancy but no watermark: the peak series
    # is OMITTED, never rendered as zero
    class NoPeakDev(FakeDev):
        def memory_stats(self):
            return {"bytes_in_use": self._b}

    fake_jax.local_devices = lambda: [NoPeakDev(4_000_000)]
    out, _ = M.sample_tpu_metrics(explain=True)
    assert out == {M.TPU_HBM_USED: 4.0}

    # non-TPU devices must never masquerade as TPU memory
    fake_jax.local_devices = lambda: [FakeDev(4_000_000, platform="gpu"),
                                      FakeDev(4_000_000, platform="cpu")]
    out, _ = M.sample_tpu_metrics(explain=True)
    assert out == {}

    # TPU devices without stats -> live-buffer floor
    fake_jax.local_devices = lambda: [FakeDev(None)]
    fake_jax.live_arrays = lambda: [types.SimpleNamespace(nbytes=2_000_000)]
    out, reason = M.sample_tpu_metrics(explain=True)
    assert out == {M.TPU_HBM_LIVE: 2.0}
    assert reason is None

    # no stats AND no live arrays -> empty, with the primary-channel reason
    fake_jax.live_arrays = lambda: []
    out, reason = M.sample_tpu_metrics(explain=True)
    assert out == {}
    # primary-channel diagnosis survives: either libtpu is absent or its
    # runtime served no data (this image ships libtpu without local chips)
    assert ("tpumonitoring not importable" in reason
            or "no per-chip data" in reason)

    # bridge registry missing (jax version bump moved the private module/
    # attribute): FAIL SAFE — report nothing rather than call
    # local_devices(), which would initialize a second TPU client inside
    # the executor's monitor
    del fake_jax._src
    fake_jax.local_devices = lambda: (_ for _ in ()).throw(
        AssertionError("fail-safe must not touch local_devices"))
    assert M._jax_memory_stats() == {}

    # jax absent from sys.modules -> the fallback must not try to import it
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    real_import = __builtins__["__import__"] if isinstance(__builtins__, dict) \
        else __builtins__.__import__

    def guard(name, *a, **k):
        assert name != "jax", "fallback must not import jax"
        return real_import(name, *a, **k)

    monkeypatch.setattr("builtins.__import__", guard)
    assert M._jax_memory_stats() == {}


def test_horovod_real_rendezvous_inits_host_plan(monkeypatch):
    """With horovod importable, the rendezvous server must be started AND
    initialised with the host-assignment plan (reference
    horovod_driver.py:32-42 static_driver_fn) — a started-but-uninitialised
    server can never rendezvous workers. Horovod isn't installed here, so
    mock its module tree and assert the plan reaches server.init()."""
    import sys
    import types

    from tony_tpu.runtimes.horovod import (
        HorovodTaskAdapter, compute_slot_assignments,
    )

    calls = {}

    def parse_hosts(host_str):
        calls["parse"] = host_str
        return ["parsed:" + host_str]

    def get_host_assignments(hosts, min_np):
        calls["assign_args"] = (hosts, min_np)
        return ["plan-entry-0", "plan-entry-1"]

    class FakeRendezvousServer:
        def start(self):
            calls["started"] = True
            return 43210

        def init(self, plan):
            calls["init_plan"] = plan

    mods = {
        "horovod": types.ModuleType("horovod"),
        "horovod.runner": types.ModuleType("horovod.runner"),
        "horovod.runner.common": types.ModuleType("horovod.runner.common"),
        "horovod.runner.common.util": types.ModuleType("horovod.runner.common.util"),
        "horovod.runner.common.util.hosts": types.ModuleType(
            "horovod.runner.common.util.hosts"
        ),
        "horovod.runner.http": types.ModuleType("horovod.runner.http"),
        "horovod.runner.http.http_server": types.ModuleType(
            "horovod.runner.http.http_server"
        ),
    }
    mods["horovod.runner.common.util.hosts"].parse_hosts = parse_hosts
    mods["horovod.runner.common.util.hosts"].get_host_assignments = (
        get_host_assignments
    )
    mods["horovod.runner.http.http_server"].RendezvousServer = FakeRendezvousServer
    for name, mod in mods.items():
        monkeypatch.setitem(sys.modules, name, mod)

    adapter = HorovodTaskAdapter()
    host_slots = [("hostA", 2), ("hostB", 2)]
    slots = compute_slot_assignments(host_slots)
    port = adapter._start_rendezvous(host_slots, slots, test_mode=False)

    assert port == 43210
    assert calls["parse"] == "hostA:2,hostB:2"
    assert calls["assign_args"] == (["parsed:hostA:2,hostB:2"], 1)
    # the critical step: the plan from get_host_assignments reaches init()
    assert calls["init_plan"] == ["plan-entry-0", "plan-entry-1"]
    # and the server object is retained so it isn't garbage collected
    assert isinstance(adapter._real_server, FakeRendezvousServer)


def test_metrics_accumulator_avg_max():
    acc = MetricsAccumulator()
    for v in (1.0, 3.0, 2.0):
        acc.observe("rss", v)
    snap = {m["name"]: m["value"] for m in acc.snapshot()}
    assert snap["max_rss"] == 3.0
    assert abs(snap["avg_rss"] - 2.0) < 1e-9


# ------------------------------------------------------------ tpu provisioner

def test_tpu_provisioner_discovery_and_geometry():
    from tony_tpu.cluster.tpu import TpuPodProvisioner, slice_num_hosts

    assert slice_num_hosts("v5litepod-16") == 4
    assert slice_num_hosts("v5litepod-8") == 1
    conf = TonyConf({
        "tony.tpu.discover-command": "printf 'host-a\\nhost-b\\nhost-c\\nhost-d\\n'",
        "tony.tpu.accelerator-type": "v5litepod-16",
        "tony.worker.instances": 4,
        "tony.worker.chips": 4,
    })
    prov = TpuPodProvisioner(conf)
    assert prov.hosts == ["host-a", "host-b", "host-c", "host-d"]
    prov.validate_layout(conf)  # 4 tpu tasks on 4 hosts: ok

    over = TonyConf({
        "tony.cluster.static-hosts": "h1,h2",
        "tony.worker.instances": 3,
        "tony.worker.chips": 4,
    })
    prov2 = TpuPodProvisioner(over)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="slice hosts"):
        prov2.validate_layout(over)


def test_tpu_provisioner_host_count_mismatch():
    import pytest as _pytest
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf = TonyConf({
        "tony.cluster.static-hosts": "h1,h2,h3",
        "tony.tpu.accelerator-type": "v5litepod-16",  # expects 4 hosts
        "tony.worker.instances": 1,
        # the mismatch is re-probed discover-retries times; the default
        # 10s inter-attempt poll made this unit ~20s of pure sleep
        # (ROADMAP tier-1 budget item)
        "tony.tpu.create-poll-interval-s": 0,
    })
    with _pytest.raises(ValueError, match="hosts"):
        TpuPodProvisioner(conf)


def test_step_timer():
    from tony_tpu.train.profiling import StepTimer

    t = StepTimer(window=5)
    for _ in range(6):
        t.tick()
    assert t.steps_per_sec > 0


# ------------------------------------------------------------ container launch

def test_build_container_command():
    from tony_tpu.conf import TonyConf
    from tony_tpu.utils.containers import build_container_command, container_enabled

    conf = TonyConf({
        "tony.docker.enabled": True,
        "tony.docker.containers.image": "img:1",
        "tony.docker.containers.mount": "/data:/data:ro,/ckpt:/ckpt",
        "tony.docker.extra-args": "--device,/dev/accel0",
    })
    assert container_enabled(conf)
    argv = build_container_command(
        "python t.py", {"TONY_JOB_NAME": "worker"}, conf, work_dir="/wd"
    )
    assert argv[:5] == ["docker", "run", "--rm", "--network", "host"]
    assert argv[-4:] == ["img:1", "bash", "-c", "python t.py"]
    pairs = set(zip(argv, argv[1:]))
    assert {("--user", f"{os.getuid()}:{os.getgid()}"), ("-v", "/wd:/wd"),
            ("-w", "/wd"), ("-v", "/data:/data:ro"), ("-v", "/ckpt:/ckpt"),
            ("-e", "TONY_JOB_NAME=worker"),
            ("--device", "/dev/accel0")} <= pairs, argv


def test_container_per_role_image_and_missing_image():
    import pytest as _pytest

    from tony_tpu.conf import TonyConf
    from tony_tpu.utils.containers import build_container_command

    conf = TonyConf({
        "tony.docker.enabled": True,
        "tony.docker.containers.image": "base:1",
        "tony.docker.evaluator.image": "eval:2",
    })
    assert "eval:2" in build_container_command("c", {}, conf, role="evaluator")
    assert "base:1" in build_container_command("c", {}, conf, role="worker")
    with _pytest.raises(ValueError, match="image"):
        build_container_command("c", {}, TonyConf({"tony.docker.enabled": True}))


def _slice_conf(tmp_path, n_hosts=4, ready_after=0, accel="v5litepod-16",
                **extra):
    """Lifecycle conf wired to the stub cloud CLI (state dir = tmp_path)."""
    stub = Path(__file__).parent / "fixtures" / "scripts" / "stub_slice.py"
    d = tmp_path / "slice"
    return TonyConf({
        "tony.tpu.discover-command": f"{PY} -S {stub} describe {d}",
        "tony.tpu.create-command":
            f"{PY} -S {stub} create {d} {n_hosts} {ready_after}",
        "tony.tpu.delete-command": f"{PY} -S {stub} delete {d}",
        "tony.tpu.accelerator-type": accel,
        "tony.tpu.create-timeout-s": 15,
        "tony.tpu.create-poll-interval-s": 0.02,
        # keep tests fast: absence is expected in most scenarios, so don't
        # armor against flakes (the flake test overrides this)
        "tony.tpu.discover-retries": 1,
        **extra,
    }), d


def test_tpu_slice_create_await_ready_teardown(tmp_path):
    """No pre-created slice: the provisioner materializes one, polls
    through the CREATING phase to READY, and teardown deletes it — the
    capacity-allocation half of the reference RM
    (TonyClient.submitApplication:317-353, async grants
    ApplicationMaster.java:1100-1119)."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path, ready_after=2)
    prov = TpuPodProvisioner(conf)
    assert prov.created
    assert prov.hosts == [f"host{i}-g1" for i in range(4)]
    assert (d / "slice.json").exists()
    prov.teardown()
    assert not (d / "slice.json").exists()


def test_tpu_slice_recreate_on_preemption(tmp_path):
    """A pre-created slice is NOT driver-owned (teardown leaves it), but
    once preemption destroys it, refresh() re-creates — and from then on
    the driver owns the replacement."""
    import subprocess as sp

    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)
    sp.run(str(conf.get("tony.tpu.create-command")), shell=True, check=True)
    prov = TpuPodProvisioner(conf)
    assert not prov.created  # discovered, not created
    assert prov.hosts == [f"host{i}-g1" for i in range(4)]
    prov.teardown()
    assert (d / "slice.json").exists(), "teardown must not delete user slices"

    (d / "slice.json").unlink()  # spot preemption destroys the slice
    prov.refresh()
    assert prov.created
    assert prov.hosts == [f"host{i}-g2" for i in range(4)], \
        "recreated slice must re-discover NEW host addresses"
    prov.teardown()
    assert not (d / "slice.json").exists()


def test_tpu_slice_create_timeout_deletes_leak(tmp_path):
    """A slice that never reaches READY fails allocation with a clear
    timeout instead of hanging the driver — and the created-but-unready
    slice is deleted, not leaked as untracked billable capacity."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(
        tmp_path, ready_after=10_000,
        **{"tony.tpu.create-timeout-s": 0.2},
    )
    with pytest.raises(TimeoutError, match="not READY"):
        TpuPodProvisioner(conf)
    assert not (d / "slice.json").exists(), "unready slice leaked"


def test_tpu_slice_carcass_cleared_before_create(tmp_path):
    """Submitting while a preemption carcass (wrong host count) still holds
    the slice name: the provisioner deletes the remnant first so the cloud
    create doesn't fail with 'already exists'."""
    import subprocess as sp

    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)  # create command makes 4 hosts
    stub = Path(__file__).parent / "fixtures" / "scripts" / "stub_slice.py"
    sp.run(f"{PY} -S {stub} create {d} 2 0", shell=True, check=True)  # carcass
    prov = TpuPodProvisioner(conf)
    assert prov.created
    assert prov.hosts == [f"host{i}-g2" for i in range(4)]
    assert "delete" in (d / "delete.log").read_text()


def test_tpu_slice_transient_discovery_flake_does_not_destroy(tmp_path):
    """One transient describe failure (API 5xx, timeout) must NOT make the
    lifecycle path delete+recreate healthy capacity: discovery is retried
    tony.tpu.discover-retries times before the slice is declared gone."""
    import subprocess as sp

    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)
    stub = Path(__file__).parent / "fixtures" / "scripts" / "stub_slice.py"
    sp.run(f"{PY} -S {stub} create {d} 4 0", shell=True, check=True)
    flaked = tmp_path / "flaked"
    conf.set(
        "tony.tpu.discover-command",
        # first call fails (transient), later calls describe normally
        f"if [ ! -f {flaked} ]; then touch {flaked}; echo 5xx >&2; exit 1; "
        f"else {PY} -S {stub} describe {d}; fi",
    )
    conf.set("tony.tpu.discover-retries", 3)
    prov = TpuPodProvisioner(conf)
    assert not prov.created, "flake must not trigger the create path"
    assert prov.hosts == [f"host{i}-g1" for i in range(4)]
    assert not (d / "delete.log").exists(), "healthy slice was deleted"


def test_tpu_slice_sustained_outage_refuses_delete_recreate(tmp_path):
    """A discovery outage longer than the whole retry budget — but with NO
    positive not-found evidence (5xx-style stderr) — must abort instead of
    engaging delete+recreate: the slice may be healthy capacity the driver
    does not own, and 'describe kept failing' is not proof it is gone."""
    import subprocess as sp

    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)
    sp.run(str(conf.get("tony.tpu.create-command")), shell=True, check=True)
    conf.set(
        "tony.tpu.discover-command",
        "echo 'ERROR: backend error 503' >&2; exit 1",
    )
    conf.set("tony.tpu.discover-retries", 2)
    with pytest.raises(RuntimeError, match="refusing to delete"):
        TpuPodProvisioner(conf)
    assert not (d / "delete.log").exists(), \
        "transient outage destroyed a healthy slice"
    assert (d / "slice.json").exists()


def test_tpu_slice_custom_not_found_pattern(tmp_path):
    """A CLI whose absent-resource message doesn't match the default
    pattern still engages the lifecycle path once
    tony.tpu.not-found-pattern names it."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)
    stub = Path(__file__).parent / "fixtures" / "scripts" / "stub_slice.py"
    flagged = tmp_path / "created_once"
    # before the create runs, describe reports an unusual absence message;
    # the create command drops a marker so later describes hit the stub
    conf.set(
        "tony.tpu.discover-command",
        f"if [ -f {flagged} ]; then {PY} -S {stub} describe {d}; "
        f"else echo 'no such resource in project' >&2; exit 1; fi",
    )
    base_create = str(conf.get("tony.tpu.create-command"))
    conf.set("tony.tpu.create-command", f"touch {flagged} && {base_create}")
    # default pattern would refuse ("no such resource" matches nothing)
    with pytest.raises(RuntimeError, match="refusing to delete"):
        TpuPodProvisioner(conf)
    conf.set("tony.tpu.not-found-pattern", "no such resource")
    prov = TpuPodProvisioner(conf)
    assert prov.created
    assert prov.hosts == [f"host{i}-g1" for i in range(4)]


def test_tpu_slice_malformed_not_found_pattern_fails_fast(tmp_path):
    """An unbalanced-paren tony.tpu.not-found-pattern is a config error at
    provisioner construction — before any cloud I/O — not an re.error
    surfacing mid-await-READY where cleanup would misread it as a failed
    create and delete the slice."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, d = _slice_conf(tmp_path)
    conf.set("tony.tpu.not-found-pattern", "not found (")
    with pytest.raises(ValueError, match="not-found-pattern"):
        TpuPodProvisioner(conf)
    assert not (d / "create.log").exists(), "config error ran the create"


def test_tpu_slice_create_without_discovery_fails_fast(tmp_path):
    """create-command with no discover mechanism is a config error reported
    immediately, not a 30-minute await-READY against nothing."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf = TonyConf({
        "tony.tpu.create-command": "true",
        "tony.tpu.discover-retries": 1,
        "tony.tpu.create-poll-interval-s": 0.01,
    })
    with pytest.raises(ValueError, match="no way to await READY"):
        TpuPodProvisioner(conf)


def test_tpu_multislice_requires_slice_placeholder(tmp_path):
    """num-slices > 1 with a lifecycle template missing {slice} is a config
    error, not N operations against ONE cloud resource (double-booked
    hosts, a slice-1 refresh deleting slice 0's capacity)."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    base = {
        "tony.tpu.num-slices": 2,
        "tony.tpu.discover-retries": 1,
        "tony.tpu.create-poll-interval-s": 0.01,
    }
    conf = TonyConf({**base, "tony.tpu.discover-command": "echo host0"})
    with pytest.raises(ValueError, match=r"\{slice\} placeholder"):
        TpuPodProvisioner(conf)
    # templated discover but raw delete: still rejected
    conf2 = TonyConf({
        **base,
        "tony.tpu.discover-command": "echo host-s{slice}",
        "tony.tpu.delete-command": "true",
    })
    with pytest.raises(ValueError, match="delete-command.*placeholder"):
        TpuPodProvisioner(conf2)


def test_tpu_slice_await_without_geometry_needs_stable_list(tmp_path):
    """Without tony.tpu.accelerator-type there is no expected host count;
    await-READY must not accept the first (possibly partial, mid-creation)
    non-empty list — it waits for the list to repeat across
    tony.tpu.ready-stable-polls consecutive polls (default 3)."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    conf, _ = _slice_conf(tmp_path, ready_after=2, accel="")
    prov = TpuPodProvisioner(conf)
    # the stub reports growing partials (2 then 3 hosts) before the full 4
    assert prov.hosts == [f"host{i}-g1" for i in range(4)]


def test_tpu_provisioner_refresh_rediscovers_hosts(tmp_path):
    """Driver retry must re-run discovery (a recreated spot slice has new
    addresses); static host lists are a no-op refresh."""
    from tony_tpu.cluster.tpu import TpuPodProvisioner

    state = tmp_path / "hosts.txt"
    state.write_text("old-a\nold-b\nold-c\nold-d\n")
    conf = TonyConf({
        "tony.tpu.discover-command": f"cat {state}",
        "tony.tpu.accelerator-type": "v5litepod-16",
        # no inter-retry sleeps: the partial-recreate refresh below is
        # retried discover-retries times and the default 10s poll made
        # this unit ~20s of pure sleep (ROADMAP tier-1 budget item)
        "tony.tpu.create-poll-interval-s": 0,
    })
    prov = TpuPodProvisioner(conf)
    assert prov.hosts == ["old-a", "old-b", "old-c", "old-d"]
    state.write_text("new-a\nnew-b\nnew-c\nnew-d\n")  # slice recreated
    prov.refresh()
    assert prov.hosts == ["new-a", "new-b", "new-c", "new-d"]

    # a partially-recreated slice (wrong host count) must be rejected,
    # keeping the previous host list
    import pytest
    state.write_text("half-a\nhalf-b\n")
    with pytest.raises(ValueError, match="recreating"):
        prov.refresh()
    assert prov.hosts == ["new-a", "new-b", "new-c", "new-d"]

    static = TpuPodProvisioner(TonyConf({
        "tony.cluster.static-hosts": "h1,h2",
    }))
    static.refresh()
    assert static.hosts == ["h1", "h2"]


# ------------------------------------------------- multislice env contract

def test_jax_adapter_multislice_requires_slice0_host(monkeypatch):
    """TONY_NUM_SLICES>1 without TONY_SLICE0_HOST must fail fast at env-build
    time — otherwise MEGASCALE_COORDINATOR_ADDRESS would be the malformed
    ':8080' and libtpu would fail much later with an opaque transport error."""
    from tony_tpu import constants as c
    from tony_tpu.runtimes.base import TaskContext
    from tony_tpu.runtimes.jax_runtime import JaxTaskAdapter

    ctx = TaskContext(
        job_name="worker", task_index=0, task_num=2, num_total_tasks=2,
        is_chief=True, command="true",
        cluster_payload={"cluster": {"worker": ["h0:1", "h1:1"]},
                         "ranks": {"worker:0": 0, "worker:1": 1},
                         "num_processes": 2,
                         "coordinator_address": "h0:1"},
        base_child_env={},
    )
    adapter = JaxTaskAdapter()

    monkeypatch.setenv(c.ENV_NUM_SLICES, "2")
    monkeypatch.setenv(c.ENV_SLICE_ID, "1")
    monkeypatch.delenv(c.ENV_SLICE0_HOST, raising=False)
    with pytest.raises(RuntimeError, match="TONY_SLICE0_HOST"):
        adapter.build_env(ctx)
    monkeypatch.setenv(c.ENV_SLICE0_HOST, "")
    with pytest.raises(RuntimeError, match="TONY_SLICE0_HOST"):
        adapter.build_env(ctx)

    monkeypatch.setenv(c.ENV_SLICE0_HOST, "slice0-host")
    env = adapter.build_env(ctx)
    assert env["MEGASCALE_COORDINATOR_ADDRESS"] == (
        f"slice0-host:{c.MEGASCALE_PORT}")
    assert env["MEGASCALE_NUM_SLICES"] == "2"
    assert env["MEGASCALE_SLICE_ID"] == "1"
