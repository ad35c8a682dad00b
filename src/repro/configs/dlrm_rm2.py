"""dlrm-rm2 [recsys]: n_dense=13 n_sparse=26 embed_dim=64
bot_mlp=13-512-256-64 top_mlp=512-512-256-1 interaction=dot
[arXiv:1906.00091].

The paper's own public-dataset baseline model.  Production cardinalities
follow the Criteo-terabyte scale (total ~204M rows x 64 dims = 52 GB fp32
-> the SHARK compression target).

One 16 GB TPU v5e chip cannot hold that table, so the drivers run
``CHIP_CFG`` there: the published widths (26 fields, dim 64, 13 dense,
MLPs 512-256-64 and 512-512-256-1) with each field's vocabulary capped
at ``CHIP_ROW_CAP`` = 2M rows — 13,116,632 rows, 3.36 GB in fp32
(``REDUCED`` states the cut).  The smoke config stays for CPU tests.
"""

from repro.configs.common import RecsysArch
from repro.data.criteo import CriteoConfig, CriteoSynth
from repro.models import recsys as R

# Criteo-terabyte-like cardinalities for the 26 sparse fields (public
# dataset statistics, rounded; dominated by a few huge id spaces)
CARDS = (
    40_000_000, 39_060, 17_295, 7_424, 20_265, 3, 7_122, 1_543, 63,
    40_000_000, 3_067_956, 405_282, 10, 2_209, 11_938, 155, 4, 976, 14,
    40_000_000, 40_000_000, 40_000_000, 590_152, 12_973, 108, 36,
)

FULL_CFG = R.DLRMConfig(cardinalities=CARDS, embed_dim=64, num_dense=13,
                        bot_mlp=(512, 256, 64),
                        top_mlp=(512, 512, 256, 1))

# one chip's share of the vocabulary: the fp32 training table plus the
# compressed step's dense (V, D) gradient must fit 16 GB of HBM
CHIP_ROW_CAP = 2_000_000
CHIP_CFG = R.DLRMConfig(
    cardinalities=tuple(min(c, CHIP_ROW_CAP) for c in CARDS),
    embed_dim=FULL_CFG.embed_dim, num_dense=FULL_CFG.num_dense,
    bot_mlp=FULL_CFG.bot_mlp, top_mlp=FULL_CFG.top_mlp)
REDUCED = {"cardinalities": "each field capped at 2M rows for one 16 GB "
                            "v5e chip (13,116,632 rows, 3.36 GB fp32)"}

_smoke_ds = CriteoSynth(CriteoConfig(num_fields=8, important_fields=4,
                                     num_dense=5))
SMOKE_CFG = R.DLRMConfig(
    cardinalities=tuple(int(c) for c in _smoke_ds.cards), embed_dim=16,
    num_dense=5, bot_mlp=(32, 16), top_mlp=(32, 1))


def arch() -> RecsysArch:
    return RecsysArch(name="dlrm-rm2", model=R.make_dlrm(FULL_CFG),
                      smoke_model=R.make_dlrm(SMOKE_CFG), has_dense=True,
                      num_dense=13, chip_model=R.make_dlrm(CHIP_CFG),
                      reduced=REDUCED)
