"""A model stored as data: the node lists in the configuration file itself."""

from chipbench.reference import Model, from_node_lists


def build(config: dict, seed: int) -> Model:
    """The stored trees; the same whatever the seed."""
    del seed
    return from_node_lists(config["model"]["trees"], config["n_attrs"], config["n_classes"])
