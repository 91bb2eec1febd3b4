"""Drive bench/run.py on the CPU at a tiny size, for the tests.

    python bench/tests/cpu_driver.py <root> <fault> -- <run.py arguments>

The look for a chip and the chip-only readings (peak memory, the peaks
table, the device planes of a trace) are stubbed here and only here. With
`fault` other than `none`, the program's timed path is broken underneath
before the run (bench/faults.py).
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main() -> int:
    root, fault = sys.argv[1], sys.argv[2]
    args = sys.argv[sys.argv.index("--") + 1:]
    import jax

    import cell
    import counts
    import devtrace
    import run
    cell.ROOT = Path(root)
    run.require_devices = lambda chips: jax.devices()[:chips]
    run.peak_bytes = lambda devices: 1
    real_peaks = counts.peaks
    counts.peaks = lambda kind: real_peaks("TPU v5 lite")
    real_load = devtrace.load

    def host_as_device(path):
        # a CPU trace has no TPU plane: its host events stand in for one
        devices, host = real_load(path)
        return [devtrace.DeviceOps(0, [e for e in host if e[2] > e[1]])], host
    devtrace.load = host_as_device
    if fault != "none":
        import faults
        faults.plant(fault)
    return run.main(args)


if __name__ == "__main__":
    sys.exit(main())
