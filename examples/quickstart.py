"""Quickstart: the paper's flow in 60 lines — QAT ResNet-9 at an arbitrary
bit-width -> ``repro.compile()`` (streamline passes + HW lowering) -> jitted
``DeployedModel`` -> few-shot NCM classification, with train/deploy numerics
identical.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core.quant import fake_quant
from repro.data.synthetic import SyntheticImages
from repro.fsl import ncm
from repro.models import resnet9
from repro.runtime import use_compile_cache

use_compile_cache()
WIDTH = 8

# 1. pick a bit-width configuration (the paper's deployment point: conv
#    6 bits = 1 int + 5 frac; activations 4 bits = 2 int + 2 frac)
qcfg = repro.QuantConfig.paper_w6a4()
print(f"weights {qcfg.weight.describe()}  activations {qcfg.act.describe()}")

# 2. a QAT backbone (here: random init; examples/fsl_train.py trains it)
params = resnet9.init_params(jax.random.PRNGKey(0), WIDTH)

# 3. compile: export the FINN-style graph (with the PyTorch-export transpose
#    artifacts of paper Fig. 4), run the registered "resnet9" recipe through
#    the PassManager — mis-ordered recipes raise PassOrderError instead of
#    silently mis-building — and lower to one jitted program.
dm = repro.compile(params, qcfg, recipe="resnet9")
print(dm.report())

# 4. consistency: model forward == deployed artifact, bit for bit
data = SyntheticImages(n_base=4, n_novel=5, seed=0)
ep = data.episode(np.random.default_rng(0), n_way=5, k_shot=5, n_query=5)
x = fake_quant(jnp.asarray(ep["query_x"]), qcfg.act)   # input contract: on-grid
f_model = resnet9.forward(params, jnp.asarray(ep["query_x"]), qcfg, WIDTH)
f_hw = dm(x)
np.testing.assert_allclose(np.asarray(f_model), np.asarray(f_hw),
                           rtol=1e-4, atol=1e-5)
print("model == DeployedModel  ✓")

# 5. few-shot classification with the NCM head (host side)
sf = dm(fake_quant(jnp.asarray(ep["support_x"]), qcfg.act))
acc = ncm.ncm_accuracy(jnp.asarray(f_hw), jnp.asarray(ep["query_y"]),
                       jnp.asarray(sf), jnp.asarray(ep["support_y"]), 5)
print(f"5-way 5-shot episode accuracy (untrained backbone): {float(acc):.2f}")
