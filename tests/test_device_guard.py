"""device_type=tpu means a TPU: every entry point resolves its device
through utils/device.resolve_device, which is fatal when the platform
that initialized is not the one named — JAX's own fallback (a warning,
then the CPU backend, exit 0) must never stand in for a missing chip.
This suite runs on the CPU backend, which is exactly the "no chip" case.
"""

import os
import subprocess
import sys

import pytest

from lightgbm_tpu.serving.forest import ServingForest
from lightgbm_tpu.utils.device import resolve_device
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.log import LightGBMError

from test_predict_fast import BINARY_MODEL

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable] + argv, env=env, cwd=str(cwd),
                          capture_output=True, text=True, timeout=300)


def test_resolve_device_on_the_cpu_backend():
    assert resolve_device("") == "cpu"
    assert resolve_device("cpu") == "cpu"
    with pytest.raises(LightGBMError, match="device_type=tpu but JAX "
                                            "initialized platform=cpu"):
        resolve_device("tpu")


def test_device_type_tpu_refuses_the_host_serving_engine():
    from lightgbm_tpu.config import Config
    with pytest.raises(LightGBMError, match="contradicts serve_backend"):
        Config.from_params({"task": "serve", "device_type": "tpu",
                            "serve_backend": "native",
                            "input_model": "m.txt"})


def test_cli_train_device_type_tpu_without_a_chip_exits_nonzero(tmp_path):
    data = tmp_path / "d.tsv"
    data.write_text("".join("%d\t%d\t%d\n" % (i % 2, i, i * 7 % 13)
                            for i in range(200)))
    proc = _run(["-m", "lightgbm_tpu", "task=train", "data=" + str(data),
                 "objective=binary", "num_trees=1", "device_type=tpu",
                 "output_model=" + str(tmp_path / "m.txt")], tmp_path)
    assert proc.returncode != 0, (proc.stdout, proc.stderr)
    assert "device_type=tpu but JAX initialized platform=cpu" \
        in proc.stderr, proc.stderr
    assert not (tmp_path / "m.txt").exists()


def test_cli_multi_machine_train_resolves_after_the_runtime_is_up(tmp_path):
    """num_machines>1: jax.distributed.initialize refuses once a backend
    is live, so the CLI may only PIN the platform before
    init_distributed and must initialize + check the backend after it.
    Two real `python -m lightgbm_tpu` ranks; a resolve_device ahead of
    the runtime would spend the whole connect deadline and fail."""
    import socket
    data = tmp_path / "d.tsv"
    data.write_text("".join("%d\t%d\t%d\n" % (i % 2, i, i * 7 % 13)
                            for i in range(400)))
    socks = [socket.socket() for _ in range(2)]
    for s in socks:
        s.bind(("localhost", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    mlist = tmp_path / "machines.txt"
    mlist.write_text("".join("127.0.0.1 %d\n" % p for p in ports))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)   # one CPU device per rank
    procs = [subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "task=train",
         "data=" + str(data), "objective=binary", "tree_learner=data",
         "num_machines=2", "machine_list_file=" + str(mlist),
         "local_listen_port=%d" % ports[r], "num_trees=2", "num_leaves=4",
         "min_data_in_leaf=5", "device_type=cpu",
         "dist_connect_deadline_s=60", "is_save_binary_file=false",
         "output_model=" + str(tmp_path / ("m%d.txt" % r))],
        env=env, cwd=str(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(2)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for r, p in enumerate(procs):
        assert p.returncode == 0, "rank %d:\n%s" % (r, outs[r])
        up = outs[r].index("Distributed runtime up")
        # the global device list: both ranks' CPU devices
        assert outs[r].index("Device: platform=cpu") > up, outs[r]
        assert "count=2" in outs[r], outs[r]
    assert (tmp_path / "m0.txt").read_text() \
        == (tmp_path / "m1.txt").read_text()


def test_chip_smoke_without_a_chip_exits_before_any_work(tmp_path):
    proc = _run([os.path.join(REPO, "chip_smoke.py")], tmp_path)
    assert proc.returncode != 0, (proc.stdout, proc.stderr)
    assert "platform=cpu" in proc.stdout
    assert "nothing was run" in proc.stderr
    # no phase ran (data generation is the first) and no result line
    assert "phase" not in proc.stdout and '"ok"' not in proc.stdout


def test_serve_engine_auto_says_so_when_jax_does_not_import(
        monkeypatch, capsys):
    """auto -> host engine only on a failed jax import, and never
    silently; an explicit backend=jax raises, and so does auto under
    device_type=tpu."""
    monkeypatch.setitem(sys.modules, "jax", None)   # import jax -> ImportError
    # a test that ran before on this worker may have left verbosity=-1
    monkeypatch.setattr(log, "_level", log.WARNING)
    forest = ServingForest(BINARY_MODEL, backend="auto")
    assert forest.engine == "host"
    assert "jax does not import" in capsys.readouterr().out
    with pytest.raises(ImportError):
        ServingForest(BINARY_MODEL, backend="jax")
    with pytest.raises(ImportError):
        ServingForest(BINARY_MODEL, backend="auto", device_type="tpu")
    with pytest.raises(ImportError):
        resolve_device("tpu")


def test_serving_built_outside_the_cli_still_checks_the_device(tmp_path):
    """A forest or server embedded through the API never passed
    cli.run's resolve_device: the forest's engine selection does the
    check itself, so device_type=tpu cannot be answered from the CPU
    backend or the host engine."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving.server import ServingServer
    for backend in ("auto", "jax"):
        with pytest.raises(LightGBMError, match="initialized platform=cpu"):
            ServingForest(BINARY_MODEL, backend=backend, device_type="tpu")
    model = tmp_path / "m.txt"
    model.write_text(BINARY_MODEL)
    cfg = Config.from_params({"task": "serve", "input_model": str(model),
                              "serve_port": "0", "device_type": "tpu"})
    with pytest.raises(LightGBMError, match="initialized platform=cpu"):
        ServingServer(cfg)
    # a forest the caller built is held to the config's device too
    with pytest.raises(LightGBMError, match="initialized platform=cpu"):
        ServingServer(cfg, forest=ServingForest(BINARY_MODEL))
    with pytest.raises(LightGBMError, match="host engine"):
        ServingServer(cfg, forest=ServingForest(BINARY_MODEL,
                                                backend="native"))
