"""repro: SHARK (CIKM'23) embedding-layer compression as a JAX framework.

Layers:
  repro.core      - the paper's contribution: F-Permutation + F-Quantization
  repro.models    - model zoo (recsys / LM transformers / GNN)
  repro.data      - synthetic data pipelines
  repro.optim     - pure-JAX optimizers + gradient compression
  repro.dist      - sharding rules and collectives
  repro.train     - train/serve steps and the fault-tolerant loop
  repro.ckpt      - checkpoint manager
  repro.kernels   - Pallas TPU kernels (validated with interpret=True)
  repro.configs   - one config per assigned architecture
  repro.launch    - mesh / dryrun / train / serve entry points
"""

import pathlib

__version__ = "1.0.0"

# the checkout this package runs from: src/repro/__init__.py -> root
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
