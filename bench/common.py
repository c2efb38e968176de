"""What the window drivers share: the numbers compared for ``correct``
and the program's configuration as a cell runs it."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes at or under
    ``limit`` (a limit of 0 asks for an exact match)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def program_config(cfg: dict):
    """The program's ``LargeVisConfig``: its defaults, with every field
    that the configuration file names set from the file (``routing`` as
    the fields of a ``RoutingConfig``)."""
    from repro.configs.largevis_default import LargeVisConfig, RoutingConfig
    fields = {f.name for f in dataclasses.fields(LargeVisConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    if "routing" in kw:
        kw["routing"] = RoutingConfig(**kw["routing"])
    return LargeVisConfig(**kw)
