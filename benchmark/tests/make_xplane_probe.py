"""How ``data/xplane_v5e_probe.pb.gz`` was recorded (TPU v5 lite, jax 0.9.0,
through the chip tool): one jitted step with ``jax.named_scope``s (``qkv``,
``paged_attention``, ``mlp`` inside a scan; ``sample`` over f32[32,50400]),
a Pallas kernel named ``probe_kernel``, an eager op, and host
``TraceAnnotation``s named like the engine's (``llm.step``,
``llm.step.decode_launch`` / ``decode_fetch`` / ``emit``), traced with the
Python tracer off at ``host_tracer_level`` 2 and 1; the level-1 trace is
the one kept.  It also prints what an inactive annotation costs and dumps
names and stats of sample events, which is how the place of ``op_name``
(the event METADATA's ``tf_op`` stat) was found.

    chiprun -- python benchmark/tests/make_xplane_probe.py
"""
import functools
import glob
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

OUT = "chiprun_out/probe"
os.makedirs(OUT, exist_ok=True)


def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def kernel(x):
    return pl.pallas_call(
        _k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name="probe_kernel", interpret=jax.default_backend() != "tpu",
    )(x)


@jax.jit
def step(w, x, logits):
    def layer(c, wl):
        with jax.named_scope("qkv"):
            h = c @ wl
        with jax.named_scope("paged_attention"):
            h = kernel(h)
        with jax.named_scope("mlp"):
            h = jax.nn.gelu(h @ wl)
        return h, None

    x, _ = jax.lax.scan(layer, x, w)
    with jax.named_scope("sample"):
        p = jax.nn.softmax(logits + x[:32, :1].astype(jnp.float32), axis=-1)
        tok = jnp.argmax(p * jnp.cumsum(jnp.sort(p, axis=-1), axis=-1), axis=-1)
    return x, tok


w = jnp.ones((4, 512, 512), jnp.bfloat16) * 0.01
x = jnp.ones((128, 512), jnp.bfloat16)
logits = jnp.ones((32, 50400), jnp.float32)
out = step(w, x, logits)
jax.block_until_ready(out)

# cost of an inactive annotation
t0 = time.perf_counter()
for i in range(100000):
    with jax.profiler.TraceAnnotation("llm.step.emit"):
        pass
t1 = time.perf_counter()
for i in range(100000):
    with jax.profiler.TraceAnnotation("llm.step", step=i, running=3, waiting=4):
        pass
t2 = time.perf_counter()
print(json.dumps({"inactive_annotation_us": (t1 - t0) * 10, "with_kwargs_us": (t2 - t1) * 10}))

for level in (2, 1):
    d = f"{OUT}/trace_h{level}"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = level
    ta = time.time()
    jax.profiler.start_trace(d, profiler_options=opts)
    tb = time.time()
    for i in range(5):
        with jax.profiler.TraceAnnotation("llm.step", step=i, running=3, waiting=4):
            with jax.profiler.TraceAnnotation("llm.step.decode_launch"):
                out = step(w, x, logits)
            with jax.profiler.TraceAnnotation("llm.step.decode_fetch"):
                tok = jax.device_get(out[1])
            with jax.profiler.TraceAnnotation("llm.step.emit"):
                time.sleep(0.002)
            e = x[None, :]  # an eager op
    tc = time.time()
    jax.profiler.stop_trace()
    td = time.time()
    print(json.dumps({"host_tracer_level": level, "start_s": tb - ta, "stop_s": td - tc}))

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    data = ProfileData.from_file(path)
    rep = {"planes": {}}
    for plane in data.planes:
        prow = rep["planes"].setdefault(plane.name, {})
        for line in plane.lines:
            evs = list(line.events)
            row = {"n": len(evs), "samples": []}
            seen = set()
            for e in evs:
                nm = e.name
                key = nm[:60]
                want = plane.name.startswith("/device:") or nm.startswith("llm.") or "jit_" in nm
                if not want or key in seen:
                    continue
                seen.add(key)
                if len(row["samples"]) >= (60 if plane.name.startswith("/device:") else 12):
                    break
                row["samples"].append({
                    "name": nm[:1500], "start_ns": e.start_ns, "dur_ns": e.duration_ns,
                    "stats": {str(k): str(v)[:400] for k, v in e.stats},
                })
            prow[line.name] = row
    with open(f"{OUT}/probe_h{level}.json", "w") as f:
        json.dump(rep, f, indent=1)
    print(json.dumps({p: {l: r["n"] for l, r in lines.items()} for p, lines in rep["planes"].items()})[:3000])
