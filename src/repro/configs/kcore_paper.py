"""The paper's own workload configs: the 14 SNAP graphs of Table I
(as synthetic analogues — see graph/generators.py) plus the engine config."""

from repro.core.kcore import KCoreConfig
from repro.graph.generators import SNAP_TABLE

CONFIG = KCoreConfig(mode="jacobi")
CONFIG_BEYOND = KCoreConfig(mode="block_gs", n_blocks=16)
GRAPHS = tuple(e.abbrev for e in SNAP_TABLE)
