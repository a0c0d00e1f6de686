"""PNA [arXiv:2004.05718]: 4 layers, d_hidden=75, aggregators
mean-max-min-std, scalers identity-amplification-attenuation.

Departures from the paper's model and from PyG's ``examples/pna.py``
(``models/gnn/pna.py`` implements them): one tower (no split of the
hidden width into towers); LayerNorm after the update in place of the
example's BatchNorm; no edge features in the message (the graphs this
system trains on have none); a destination with no in-neighbour gets 0
for all four aggregates. ``delta`` is a placeholder here: the measured
lane sets it from its training graph (``pna.graph_delta``).
"""
from repro.configs.registry import ArchDef
from repro.configs.shapes import GNN_SHAPES
from repro.models.gnn.pna import PNAConfig


def make_config(d_in: int = 100, n_classes: int = 47) -> PNAConfig:
    return PNAConfig(d_in=d_in, d_hidden=75, n_classes=n_classes, n_layers=4)


def make_smoke_config() -> PNAConfig:
    return PNAConfig(d_in=16, d_hidden=16, n_classes=5, n_layers=2)


ARCH = ArchDef(
    arch_id="pna", family="gnn",
    make_config=make_config, make_smoke_config=make_smoke_config,
    shapes=tuple(GNN_SHAPES),
    model_module="repro.models.gnn.pna",
)
