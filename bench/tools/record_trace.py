"""Record a small profiler trace on the chip for the trace reduction's
self-check, and print how the trace is laid out.

  python3 bench/tools/record_trace.py OUT_DIR

Programs one (256, 1024) weight on ``exact-pallas`` and runs a jitted
step (the ``pim_matmul`` kernel, then an XLA tanh) a few times inside the
harness's ``window`` span, then idles and runs it again, so the trace
holds kernel time, other device time, and idle gaps. Copies the
``.xplane.pb`` to ``OUT_DIR/small.xplane.pb`` and prints every plane and
line with its event count, and the first events of each device line with
their metadata.
"""
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro import engine
    from repro.core.pim import PimConfig

    from harness import trace as trace_mod

    out = pathlib.Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    kw, kx = jax.random.split(jax.random.PRNGKey(0))
    plan = jax.jit(lambda w: engine.program(w, PimConfig(
        substrate="exact-pallas")))(jax.random.normal(kw, (256, 1024)))
    x = jax.random.normal(kx, (64, 256))

    @jax.jit
    def step(x, plan):
        return jnp.tanh(engine.matmul(x, plan))

    step(x, plan).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("window"):
        for i in range(6):
            with jax.profiler.TraceAnnotation("step"):
                step(x, plan).block_until_ready()
            if i == 2:
                with jax.profiler.TraceAnnotation("host"):
                    time.sleep(0.01)
    jax.profiler.stop_trace()
    path = trace_mod.find_xplane(tdir)
    shutil.copy(path, out / "small.xplane.pb")
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"saved {out / 'small.xplane.pb'} "
          f"({os.path.getsize(out / 'small.xplane.pb')} bytes)")
    pd = ProfileData.from_file(str(out / "small.xplane.pb"))
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            if plane.name.startswith("/device:"):
                for ev in evs[:8]:
                    print("    EV", repr(ev.name), ev.start_ns, ev.duration_ns,
                          [(k, str(v)[:80]) for k, v in ev.stats])
            elif line.name == "python" or "python" in line.name.lower():
                for ev in evs:
                    if ev.name in ("window", "step", "host"):
                        print("    HOST", ev.name, ev.start_ns, ev.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main())
