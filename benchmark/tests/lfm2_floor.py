"""Where ``lfm2-24b-a2b-l10-1chip``'s rounding noise comes from: the served
programs in float32 (the bfloat16 weights as they are) with bfloat16 rounding
of the products' inputs switched on in ONE class of layers at a time, each
against the plain reference on the configuration's probes.

    python3 benchmark/tests/lfm2_floor.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths.  Prints, a class, the rms of
the programs' logit rows against the reference's over the 192 probe rows.  The
classes: ``conv_first`` (the convolutions of the two leading dense layers),
``conv_later``, ``attention``, ``dense``, ``experts``, ``head``, ``all`` (the
configured dtype policy but for the Pallas kernels: every class runs its XLA
form, since the kernels take bfloat16 alone) and ``none``.  This is what
decides how loud ONE place held at 3 bits of mantissa (16 times bfloat16's
step) can read beside the sound path: a class that holds a share ``f`` of the
floor's power reads about ``sqrt(1 + 255 f)`` times the floor
(``lfm2_controls.py`` has the controls; PERF.md section 6, PR 61, the
readings)."""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness as H  # noqa: E402
from benchmark import serving  # noqa: E402
from benchmark.tests.falcon_h1_controls import _sequences  # noqa: E402
from benchmark.tests.granite_h_controls import _Served  # noqa: E402

CONFIG = "lfm2-24b-a2b-l10-1chip"
CLASSES = ("conv_first", "conv_later", "attention", "dense", "experts", "head")


def _mixed(cfg, low: set):
    """``cfg``'s programs in float32 but for the classes in ``low``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import Lfm2MoeBody

    narrow, wide = jnp.dtype("bfloat16"), jnp.dtype("float32")

    class Mixed(Lfm2MoeBody):
        """Every hook in its class's precision: bfloat16 inputs at the default
        precision, or float32 at ``highest`` (a TPU's default multiplies
        float32 operands as bfloat16)."""

        def _in(self, name, hook, *a):
            self.dt = narrow if name in low else wide
            with jax.default_matmul_precision("default" if name in low else "highest"):
                return hook(*a)

        # the layer loop traces run by run, and the dense layers lead: a
        # convolution traced before the first expert layer is one of theirs
        def _conv(self):
            return "conv_later" if self.past_dense else "conv_first"

        def decode(self, *a):
            self.past_dense = False
            with jax.default_matmul_precision("highest"):
                return super().decode(*a)

        def chunk(self, *a):
            self.past_dense = False
            with jax.default_matmul_precision("highest"):
                return super().chunk(*a)

        def embed(self, *a):
            return self._in("head", super().embed, *a)

        def lm_head(self, *a):
            return self._in("head", super().lm_head, *a)

        def _conv_in(self, *a):
            return self._in(self._conv(), super()._conv_in, *a)

        def _conv_out(self, *a):
            return self._in(self._conv(), super()._conv_out, *a)

        def _qkv(self, *a):
            return self._in("attention", super()._qkv, *a)

        def _attn_out(self, *a):
            return self._in("attention", super()._attn_out, *a)

        def _dense_mlp(self, *a):
            return self._in("dense", super()._dense_mlp, *a)

        def _expert_mlp(self, *a):
            self.past_dense = True
            return self._in("experts", super()._expert_mlp, *a)

    class Config(type(cfg)):
        def serving_body(self):
            return Mixed(self)

    return Config(**dict(dataclasses.asdict(cfg), dtype="float32", attn_impl="xla"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None, help="comma-separated classes, 'all', 'none'")
    args = ap.parse_args()
    H.prepare_environment(args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    from benchmark.reference import lfm2_moe as reference
    from ray_tpu.serve.llm import _seeded_params

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    # the weights the cell serves: bfloat16 values, whatever a rehearsal's dtype
    cfg = H.family_piece(config, "model_config")(dict(sizes, dtype=config["dtype"]))
    consts = H.family_piece(config, "reference_sizes")(cfg)
    params = _seeded_params(H.family_piece(config, "program_init")(), cfg,
                            config["deployment"]["weights_seed"], 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    names = args.only.split(",") if args.only else ["none", "all", *CLASSES]
    report = {"platform": jax.devices()[0].platform, "rms": {}}
    for name in names:
        low = set(CLASSES) if name == "all" else {name}
        programs = _Served(_mixed(cfg, low), params, sizes["engine"])
        outs, rows = programs.probes(probes, keep_logits=True)
        del programs
        gc.collect()
        err = np.concatenate([
            got - np.asarray(reference.logits_and_margins(params, seq, at, consts)[0])
            for (seq, at), got in zip(_sequences(probes, outs), rows)])
        report["rms"][name] = float(np.sqrt((err**2).mean()))
        H.emit("floor", rounding=name, logit_rows_rms=report["rms"][name], rows=len(err))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
