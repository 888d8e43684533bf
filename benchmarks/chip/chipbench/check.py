"""The comparison that decides ``correct``: each statistic of the logit
gaps that a configuration's ``check`` names, beside its limit.  The
benchmark's runs and the control's readings (``control.py``) both go
through it."""

from __future__ import annotations

from typing import Dict


def checks(conf: dict, summary: dict) -> Dict[str, dict]:
    """{name: {"value", "limit"}} for each entry of ``conf["check"]``;
    the name's first word picks the statistic of ``summary``
    (``reference.summary``): "mean_logit_gap" compares the mean gap,
    "widest_logit_gap" the widest.  With no summary the value is None."""
    return {name: {"value": summary.get(name.split("_")[0]),
                   "limit": limit}
            for name, limit in conf["check"].items()}


def passed(compared: Dict[str, dict]) -> bool:
    """Every value read and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
