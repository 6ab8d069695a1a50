"""Record the small trace the reduction is checked on. Run ON THE CHIP,
alone (this process takes the chip):

    python cdtbench/tests/record_fixture.py

Three small programs with names of their own, with sleeps of known length
between them, traced without the Python tracer so that the file stays
small. Writes ``chiprun_out/cdtbench/fixture/small.xplane.pb`` and
``expected.json`` (what the host saw: program names, calls, sleeps); the
builder copies both to ``cdtbench/tests/data/``.
"""

import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parents[2] / "chiprun_out" / "cdtbench" / "fixture"
SLEEPS = (0.020, 0.050)


def prep_body(x):
    return x * 2.0 + 1.0


def seg_body(x, w):
    def step(c, _):
        return jnp.tanh(c @ w), None
    return jax.lax.scan(step, x, None, length=8)[0]


def fin_body(x):
    return jnp.clip(x.astype(jnp.float32), 0.0, 1.0).sum()


def main():
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    device = jax.devices()[0]
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 0.001, jnp.bfloat16)
    prep, seg, fin = jax.jit(prep_body), jax.jit(seg_body), jax.jit(fin_body)
    jax.block_until_ready(fin(seg(prep(x), w)))          # compile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(str(OUT / "trace"), profiler_options=options)
    t0 = time.monotonic()
    calls = []
    for sleep_s in SLEEPS:
        y = jax.block_until_ready(prep(x))
        y = jax.block_until_ready(seg(y, w))
        y = jax.block_until_ready(seg(y, w))
        jax.block_until_ready(fin(y))
        calls += ["prep_body", "seg_body", "seg_body", "fin_body"]
        time.sleep(sleep_s)
    traced_s = time.monotonic() - t0
    jax.profiler.stop_trace()
    xplane = sorted((OUT / "trace").rglob("*.xplane.pb"))[-1]
    shutil.copy(xplane, OUT / "small.xplane.pb")
    shutil.rmtree(OUT / "trace")
    (OUT / "expected.json").write_text(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "calls": calls, "sleeps_s": list(SLEEPS), "traced_s": traced_s}))
    print(f"fixture: {(OUT / 'small.xplane.pb').stat().st_size} bytes, "
          f"{traced_s:.3f} s traced on {device.device_kind}")


if __name__ == "__main__":
    main()
