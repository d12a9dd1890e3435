"""Plain reference of DEMS-A-COOP: the fleet tick of
``harness/ref_fleet.py`` with its peer transfers between ticks."""
from harness import ref_fleet


def make(cfg: dict, n_edges: int, dtype, **lanes):
    return ref_fleet.FleetRef(ref_fleet.Table(cfg["models"], dtype),
                              ref_fleet.Params.from_config(cfg, coop=True),
                              n_edges, dtype, **lanes)
