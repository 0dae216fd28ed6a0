"""The Graph500 Kronecker graph of ``kron.py``, made on the device from
the configuration's initiator and ``graph_seed`` and the run's seed."""

import kron

#: the configuration keys this generator reads
KEYS = {"scale", "edgefactor", "initiator", "graph_seed"}


def edges(config: dict, seed: int):
    a, b, c = config["initiator"]
    return kron.edges(config["scale"], config["edgefactor"], a, b, c,
                      config["graph_seed"], seed)
