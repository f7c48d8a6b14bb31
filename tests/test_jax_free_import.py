"""Pin the jax-free fast-path invariant at the process level.

graftlint's GL002 proves the IMPORT GRAPH stays jax-free by static
analysis; these tests prove the same thing dynamically — a fresh
interpreter imports the module / parses CLI args and `jax` must never
appear in sys.modules.  Either test failing without the other means the
linter's module list and reality have drifted.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_fresh(code):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert proc.stdout.strip().endswith("JAXFREE_OK"), (proc.stdout,
                                                        proc.stderr)


def test_predict_fast_import_never_touches_jax():
    _run_fresh(
        "import sys\n"
        "import lightgbm_tpu.predict_fast\n"
        "import lightgbm_tpu.models.tree\n"
        "import lightgbm_tpu.io.parser\n"
        "bad = [m for m in sys.modules if m == 'jax'"
        " or m.startswith('jax.') or m.startswith('jaxlib')]\n"
        "assert not bad, bad\n"
        "print('JAXFREE_OK')\n")


def test_cli_argparse_never_touches_jax():
    # Application.__init__ runs the full key=value + config-file parse
    # (the part of task=predict startup that precedes the native fast
    # path); none of it may pull in jax
    _run_fresh(
        "import sys\n"
        "from lightgbm_tpu.cli import Application\n"
        "app = Application(['task=predict', 'data=/nonexistent.tsv',\n"
        "                   'input_model=/nonexistent.txt',\n"
        "                   'num_model_predict=3', 'verbose=0'])\n"
        "assert app.config.task == 'predict'\n"
        "bad = [m for m in sys.modules if m == 'jax'"
        " or m.startswith('jax.') or m.startswith('jaxlib')]\n"
        "assert not bad, bad\n"
        "print('JAXFREE_OK')\n")


def test_serving_fallback_modules_never_touch_jax():
    # serve_backend=native promises the jax-free startup profile: the
    # whole serving package must import clean (the jax engine only
    # imports jax lazily when selected)
    _run_fresh(
        "import sys\n"
        "import lightgbm_tpu.serving.server\n"
        "import lightgbm_tpu.serving.forest\n"
        "import lightgbm_tpu.serving.batcher\n"
        "bad = [m for m in sys.modules if m == 'jax'"
        " or m.startswith('jax.') or m.startswith('jaxlib')]\n"
        "assert not bad, bad\n"
        "print('JAXFREE_OK')\n")


def test_analysis_linter_never_touches_jax():
    # the linter must run in the jax-free CI lane it protects
    _run_fresh(
        "import sys\n"
        "from lightgbm_tpu.analysis.graftlint import run_graftlint\n"
        "from lightgbm_tpu.analysis.typegate import run_typegate\n"
        "run_graftlint()\n"
        "run_typegate()\n"
        "bad = [m for m in sys.modules if m == 'jax'"
        " or m.startswith('jax.') or m.startswith('jaxlib')]\n"
        "assert not bad, bad\n"
        "print('JAXFREE_OK')\n")
