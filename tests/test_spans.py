"""The program's host spans (``repro.core.spans``): they never load JAX
into a process, and once JAX is there they are profiler annotations."""

import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def test_serving_and_process_backend_imports_load_no_jax():
    code = (
        "import sys\n"
        "import repro.serving.engine, repro.serving.model_runner\n"
        "import repro.cluster.process_backend\n"
        "from repro.core.spans import span\n"
        "a, b = span('revati.x.y'), span('revati.x.z', step=3)\n"
        "with a, b:\n"
        "    pass\n"
        "assert a is b, 'without JAX every span is one shared no-op'\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib')))\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


def test_spans_are_trace_annotations_once_jax_is_loaded():
    import jax

    from repro.core.spans import span
    s = span("revati.engine.step", step=4)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass
