"""Drive bench/trace_layers.py on the CPU at a tiny size, for the tests.

    python bench/tests/cpu_layers_driver.py <root> none -- <arguments>

cpu_driver.py's stubs of the chip-only readings, under trace_layers.py's
additions to run.py's reader context.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cpu_driver  # noqa: E402
import trace_layers  # noqa: E402

if __name__ == "__main__":
    trace_layers.install(tracer_only="--tracer" in sys.argv)
    if "--tracer" in sys.argv:
        i = sys.argv.index("--tracer")
        del sys.argv[i:i + 2]
    sys.exit(cpu_driver.main())
