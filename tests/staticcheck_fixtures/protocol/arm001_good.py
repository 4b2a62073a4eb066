"""known-good ARM001: the declared arm flag is a bool Config field,
read as the gate that selects between the fast path and its live
comparison arm, and a key of the perfgate-shaped fingerprint."""

import dataclasses

ARM_FLAGS = ("ag_live_arm",)


@dataclasses.dataclass
class Config:
    ag_live_arm: bool = True
    batch: int = 8


class Plane:
    def __init__(self, config):
        self._fast = bool(config.ag_live_arm)

    def ingest(self, items):
        if self._fast:
            return list(items)
        return [self.ingest_one(i) for i in items]

    def ingest_one(self, item):
        return item


def record(cfg):
    return {"fingerprint": {"ag_live_arm": bool(cfg.ag_live_arm)}}
