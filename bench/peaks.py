"""Published per-chip peaks, keyed by JAX's ``device_kind`` (``peaks.json``,
with its source).  A chip that is not in the table is an error."""
from __future__ import annotations

import os

from bench import common


def peaks(device_kind: str) -> dict:
    table = common.load_json(os.path.join(common.BENCH, "peaks.json"))
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(table['kinds'])}") from None
