"""The table of published peaks, keyed by JAX's `device_kind`. A kind that
is not in the table is an error, never a default."""
import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'peaks.json')


def peaks_of(device_kind):
    with open(_TABLE) as f:
        table = json.load(f)['by_device_kind']
    if device_kind not in table:
        raise KeyError('no published peaks for device kind %r (have: %s); add '
                       'it to %s with its source'
                       % (device_kind, sorted(table), _TABLE))
    return table[device_kind]
