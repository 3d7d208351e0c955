"""End-to-end driver: pretrain the quantized ResNet-9 backbone on base
classes, then evaluate few-shot episodes on held-out novel classes at two
bit-widths — the paper's Table II experiment in miniature.

  PYTHONPATH=src python examples/fsl_train.py [--steps 150]
"""

import argparse

from repro.core.quant import QuantConfig
from repro.data.synthetic import SyntheticImages
from repro.fsl.pipeline import FSLPipeline, evaluate_episodes, pretrain_backbone
from repro.runtime import use_compile_cache

use_compile_cache()
ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=150)
ap.add_argument("--width", type=int, default=16)
args = ap.parse_args()

data = SyntheticImages(n_base=24, n_novel=8, seed=0)
for label, qcfg in [("w6a4 (paper)", QuantConfig.paper_w6a4()),
                    ("w16a16 (conventional)", QuantConfig.paper_w16a16())]:
    pipe = FSLPipeline(width=args.width, qcfg=qcfg)
    print(f"== {label}: pretraining {args.steps} steps ==")
    out = pretrain_backbone(data, pipe, steps=args.steps, batch=32,
                            log_every=max(args.steps // 5, 1))
    acc, ci = evaluate_episodes(out["params"], data, pipe, n_episodes=20)
    print(f"{label}: 5-way 5-shot novel-class accuracy "
          f"{acc*100:.2f}% ± {ci*100:.2f}%")
    # score the same episodes through the COMPILED deployment artifact
    # (repro.compile -> jitted HW graph): deployed accuracy == QAT accuracy
    # is the paper's consistency claim, now checked on the serving datapath.
    # MultiThreshold tables have 2^act_bits - 1 levels, so the compiled path
    # is only practical at narrow widths (the paper's whole point — the
    # 16-bit "conventional" row is the baseline it beats).
    if qcfg.act.total_bits <= 8:
        acc_dep, ci_dep = evaluate_episodes(out["params"], data, pipe,
                                            n_episodes=20,
                                            feats_fn=pipe.deploy(out["params"]))
        print(f"{label}: deployed (repro.compile) accuracy "
              f"{acc_dep*100:.2f}% ± {ci_dep*100:.2f}%")
        # im2col+MVAU and the direct conv accumulate in different orders, so
        # a borderline query can flip between two near-equidistant centroids;
        # one flip over 20x75 queries is ~0.0007
        assert abs(acc_dep - acc) < 0.01, "deployed accuracy must match QAT"
