"""The one table of device peaks the benchmark divides by."""
from __future__ import annotations

import json
import os
from typing import Dict

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     'peaks.json')


def peak(device_kind: str) -> Dict[str, float]:
    with open(_PATH, 'r', encoding='utf-8') as f:
        table = json.load(f)
    if device_kind.startswith('_') or device_kind not in table:
        raise KeyError(
            f'device kind {device_kind!r} is not in {_PATH}: add its '
            f'published peaks with their source; there is no default')
    return table[device_kind]
