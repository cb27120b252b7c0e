"""Execution evidence for the tools/ scripts (VERDICT r2 weak #6: 'untested
tools rot')."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(n_dev=2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n_dev
    return env


def test_bandwidth_measure_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth",
                                      "measure.py"),
         "--size", "1", "--iters", "3"],
        env=_env(4), cwd=REPO, timeout=300, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "busbw=" in out.stdout


def test_bench_io_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_io.py"),
         "--n", "64", "--batch", "16", "--edge", "64", "--workers", "2"],
        env=_env(1), cwd=REPO, timeout=540, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    metrics = {l["metric"]: l["value"] for l in lines}
    assert metrics["io_imagerecorditer_images_per_sec"] > 0
    assert metrics["io_dataloader_images_per_sec"] > 0


def test_im2rec_pack_and_read(tmp_path):
    from PIL import Image
    import numpy as onp
    img_dir = tmp_path / "imgs" / "cls0"
    img_dir.mkdir(parents=True)
    for i in range(4):
        Image.fromarray(
            onp.random.RandomState(i).randint(0, 255, (32, 32, 3), "uint8")
        ).save(img_dir / f"im{i}.jpg")
    lst = tmp_path / "data.lst"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         str(tmp_path / "data"), str(tmp_path / "imgs"), "--list",
         "--recursive"],
        env=_env(1), cwd=REPO, timeout=180, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-1500:]
    assert lst.exists()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         str(tmp_path / "data"), str(tmp_path / "imgs")],
        env=_env(1), cwd=REPO, timeout=300, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-1500:]
    rec = str(tmp_path / "data.rec")
    assert os.path.exists(rec)
    from mxtpu.gluon.data.vision import ImageRecordDataset
    ds = ImageRecordDataset(rec)
    img, label = ds[0]
    assert img.shape[2] == 3


def test_parse_log(tmp_path):
    """parse_log extracts epochs/metrics/speed from fit+Speedometer logs
    (parity: tools/parse_log.py)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import parse_log

    log = """\
INFO Epoch[0] Batch [20]\tSpeed: 1000.00 samples/sec\taccuracy=0.5
INFO Epoch[0] Batch [40]\tSpeed: 3000.00 samples/sec\taccuracy=0.6
INFO Epoch[0] Train-accuracy=0.62
INFO Epoch[0] Time cost=10.5
INFO Epoch[0] Validation-accuracy=0.58
INFO Epoch[1] Train-accuracy=0.81
INFO Epoch[1] Validation-accuracy=0.77
"""
    parsed = parse_log.parse_log(log.splitlines())
    assert sorted(parsed) == [0, 1]
    assert parsed[0]["speed"] == [1000.0, 3000.0]
    assert parsed[0]["train"]["accuracy"] == 0.62
    assert parsed[0]["val"]["accuracy"] == 0.58
    assert parsed[0]["time"] == 10.5
    table = parse_log.format_table(parsed)
    assert "| 0 |" in table and "0.77" in table
    tsv = parse_log.format_table(parsed, fmt="tsv")
    assert tsv.splitlines()[0].startswith("epoch\t")


@pytest.mark.slow
def test_diagnose_runs():
    """diagnose dumps env/library/device info and exits 0 (parity:
    tools/diagnose.py)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        env=_env(1), cwd=REPO, timeout=240, capture_output=True,
        text=True)
    assert out.returncode == 0, out.stderr[-1500:]
    for section in ("Python Info", "Library Info", "MXTPU Info",
                    "Compile Ledger", "Device Info"):
        assert section in out.stdout
    assert "jax" in out.stdout
    # the engine-bulk probe reported into the ledger: the section shows
    # the site and a clean discipline verdict
    assert "engine.bulk" in out.stdout
    assert "discipline   : 0 error(s)" in out.stdout
    # the Pallas kernel-geometry gate ran and verdicts clean
    assert "Pallas Kernel Geometry" in out.stdout
    assert "verdict      : 0 error(s)" in out.stdout


def _load_launcher():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxtpu_launch", os.path.join(REPO, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_launcher_counts_chips_from_device_nodes_without_jax(tmp_path):
    """tpu_chips() counts the host's accelerator device nodes — one
    /dev/accel<N> or one VFIO group per chip; the launcher never imports
    JAX (that would take the chips from its workers)."""
    launch = _load_launcher()
    assert launch.tpu_chips(str(tmp_path)) == 0
    (tmp_path / "vfio").mkdir()
    for node in ("vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio",
                 "null", "accelerometer"):
        (tmp_path / node).write_text("")
    assert launch.tpu_chips(str(tmp_path)) == 4     # the v5e host seen
    (tmp_path / "accel0").write_text("")
    assert launch.tpu_chips(str(tmp_path)) == 5
    code = ("import runpy, sys\n"
            "sys.argv = ['launch.py', '-n', '1', sys.executable, '-c', "
            "'pass']\n"
            "try:\n"
            "    runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    assert not e.code, e.code\n"
            "assert 'jax' not in sys.modules\n"
            % os.path.join(REPO, "tools", "launch.py"))
    out = subprocess.run([sys.executable, "-c", code], env=_env(1),
                         cwd=REPO, timeout=120, capture_output=True,
                         text=True)
    assert out.returncode == 0, out.stderr[-2000:]


def test_launcher_refuses_local_workers_on_a_chip_host(monkeypatch):
    """N local workers start with identical environments and would all
    reach for the same chips: refused with a clear error on a chip
    host, allowed as a CPU rehearsal."""
    launch = _load_launcher()
    monkeypatch.setattr(launch, "tpu_chips", lambda: 4)
    monkeypatch.setattr(sys, "argv", ["launch.py", "-n", "2",
                                      sys.executable, "-c", "pass"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="refusing 2 local workers on a "
                                         "host with 4 accelerator chip"):
        launch.main()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as done:
        launch.main()
    assert not done.value.code
