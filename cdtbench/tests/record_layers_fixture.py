"""Record the small trace ``device_layers.py`` is checked on. Run ON THE
CHIP, alone (this process takes the chip):

    python cdtbench/tests/record_layers_fixture.py

A toy program with two ``cdt.*`` scopes and one operation under none: a
scan whose step multiplies under ``cdt.ffn`` (the fixture's MXU work, a
1024³ product a step), normalises under ``cdt.norm_mod`` and scales under
no scope. Written with ``jax.named_scope`` itself and not the program's
registry, so that the fixture depends on nothing but the names. Traced
without the Python tracer, so the file stays small. Writes
``chiprun_out/cdtbench/fixture/layers.xplane.pb`` and
``layers.expected.json`` (the shares the reader gave on the machine that
recorded it, the operations' names and the product's count); the builder
copies both to ``cdtbench/tests/data/``.
"""

import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
OUT = ROOT / "chiprun_out" / "cdtbench" / "fixture"
STEPS, N = 8, 1024


def toy_body(x, w):
    def step(c, _):
        with jax.named_scope("cdt.ffn"):
            y = jnp.tanh(c @ w)
        with jax.named_scope("cdt.norm_mod"):
            y32 = y.astype(jnp.float32)
            y = (y32 * jax.lax.rsqrt((y32 * y32).mean(-1, keepdims=True)
                                     + 1e-6)).astype(c.dtype)
        # no scope: what the reader must call (unnamed); the barrier keeps
        # the compiler from fusing it into the scoped neighbours
        y = jax.lax.optimization_barrier(y)
        return y * 0.5 + 0.25, None
    return jax.lax.scan(step, x, None, length=STEPS)[0]


def main():
    from cdtbench import device_layers

    shutil.rmtree(OUT / "layers_trace", ignore_errors=True)
    OUT.mkdir(parents=True, exist_ok=True)
    device = jax.devices()[0]
    x = jnp.ones((N, N), jnp.bfloat16)
    w = jnp.full((N, N), 0.001, jnp.bfloat16)
    toy = jax.jit(toy_body)
    jax.block_until_ready(toy(x, w))                     # compile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(str(OUT / "layers_trace"),
                             profiler_options=options)
    for _ in range(2):
        jax.block_until_ready(toy(x, w))
    jax.profiler.stop_trace()
    xplane = sorted((OUT / "layers_trace").rglob("*.xplane.pb"))[-1]
    shutil.copy(xplane, OUT / "layers.xplane.pb")
    shutil.rmtree(OUT / "layers_trace")
    answer = device_layers.report(
        device_layers.read_space(OUT / "layers.xplane.pb"),
        {"toy": "toy_body"})
    if answer is None:
        sys.exit("no /device:TPU plane with a cdt.* scope in the trace: "
                 "this script records on the chip")
    for line in device_layers.lines(answer):
        print(line)
    (OUT / "layers.expected.json").write_text(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "calls": 2, "steps": STEPS, "product_flops": 2 * N ** 3,
        "busy_s": answer["busy_s"], "named_pct": answer["named_pct"],
        "seconds": {k: v["seconds"] for k, v in answer["layers"].items()},
        "ops": {k: v["ops"] for k, v in answer["layers"].items()},
        "flops": {k: v["flops"] for k, v in answer["layers"].items()},
        "top": {k: [row["at"] for row in v]
                for k, v in answer["top"].items()}}, indent=1) + "\n")
    print(f"fixture: {(OUT / 'layers.xplane.pb').stat().st_size} bytes on "
          f"{device.device_kind}")


if __name__ == "__main__":
    main()
