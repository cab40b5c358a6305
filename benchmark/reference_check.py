"""Teacher-forced check of served tokens against the plain reference.

    python benchmark/reference_check.py <job.json> <verdict.json>

A process of its own: it opens the chip, so it runs only after the
replica has let go of it.  The served path returns tokens, not logits, so
for every probe the reference reads the probe's prompt followed by the
tokens the engine chose, in one full forward pass, and at every output
position the reference's logit of the engine's token must lie within
``logit_tolerance`` of the reference's largest logit there.  With random
weights the largest logit changes on rounding, which is why the tokens
themselves are not compared.  The weights are the ones the replica
served: the program's seeded initializer, same seed, same dtype.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as H  # noqa: E402


def main(argv: list) -> None:
    job_path, out_path = argv
    with open(job_path) as f:
        job = json.load(f)
    config, rehearsal = job["config"], job["rehearsal"]
    sizes = H.sizes(config, rehearsal)

    import jax
    import numpy as np

    from ray_tpu.serve.llm import _seeded_params

    if not rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("reference check: no TPU")
    # model, initializer and reference are the configuration's FAMILY's
    cfg = H.family_piece(config, "model_config")(sizes)
    init = H.family_piece(config, "program_init")()
    reference_logits = H.family_piece(config, "reference_logits")
    params = _seeded_params(init, cfg, config["deployment"]["weights_seed"], 1)
    tol = sizes["correctness"]["logit_tolerance"]
    width = max(len(p["prompt"]) + len(o) for p, o in zip(job["probes"], job["outs"]))
    deficits = []
    for probe, out in zip(job["probes"], job["outs"]):
        prompt = probe["prompt"]
        seq = (prompt + out[:-1] + [0] * width)[:width]  # causal: padding is inert
        rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(out)))
        logits = np.asarray(reference_logits(params, seq, rows, cfg))
        chosen = logits[np.arange(len(out)), np.asarray(out)]
        deficits.append([float(x) for x in logits.max(axis=-1) - chosen])
    worst = max(max(d) for d in deficits)
    verdict = {
        "ok": bool(worst <= tol), "max_deficit": worst, "tolerance": tol,
        "positions": sum(len(d) for d in deficits),
        "per_probe_max": [max(d) for d in deficits],
        "platform": jax.devices()[0].platform,
    }
    with open(out_path, "w") as f:
        json.dump(verdict, f)
    print(json.dumps({"event": "reference_check", **verdict}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
