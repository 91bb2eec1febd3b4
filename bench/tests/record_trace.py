"""Record the small chip trace that test_devtrace.py reads.

    python3 bench/tests/record_trace.py bench/tests/data/v5e_axpy_flash.xplane.pb

Run on one TPU chip: profiles a few calls of the program's two Pallas
kernels (seeded_axpy, flash attention) at OPT-125M widths, opened by the
`bench_window_open` annotation the benchmark writes, and copies the
`.xplane.pb` to the path given.
"""
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py needs a TPU")
    w = jnp.zeros((768, 3072), jnp.float32)
    q = jnp.ones((2, 12, 64, 64), jnp.float32)
    axpy = jax.jit(lambda w, s: ops.seeded_axpy(w, s, 1e-3))
    attn = jax.jit(lambda q: ops.attention(q, q, q))
    jax.block_until_ready((axpy(w, jnp.uint32(1)), attn(q)))
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window_open"):
        pass
    for i in range(3):
        w = axpy(w, jnp.uint32(i))
        jax.block_until_ready(attn(q + w[0, 0]))
    jax.profiler.stop_trace()
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
