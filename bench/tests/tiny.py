"""A cell's configuration at the reduced widths of its file's ``cpu``
block, for runs on the CPU: the block's ``model`` and ``serving`` sizes
replace the published ones, and its ``check`` holds the tiny size's own
limits (the block's ``why`` gives the readings they were set from)."""
import copy


def shrink(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    for part in ("model", "serving", "check"):
        cfg[part].update(cfg["cpu"][part])
    return cfg
