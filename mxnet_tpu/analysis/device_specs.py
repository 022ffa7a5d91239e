"""Device-spec table for the static roofline cost model.

A roofline classification (Williams et al., CACM 2009) needs two device
numbers: peak FLOP/s and peak HBM bytes/s; their ratio is the *machine
balance* (flop/byte) that separates compute-bound from bandwidth-bound
graphs. Two kinds of entries live here:

* ``*-spec`` — the datasheet numbers (what the silicon promises);
* ``bench-r05`` — a HISTORICAL entry: what an early round of this repo
  read on a development device (round 5: 95.25 TFLOP/s matmul peak,
  62.5 GB/s saxpy HBM, machine balance 1524 flop/B). It is still the
  default because the lint thresholds and their tests were calibrated
  against it; it does not describe a v5e chip. Name ``v5e-spec`` to
  plan against the datasheet.

``MXNET_ANALYSIS_DEVICE_SPEC`` overrides the default: either the name
of a table entry (``v5e-spec``) or a path to a JSON file with the same
keys (docs/static-analysis.md documents the override).
"""

import json
import os

__all__ = ['DEVICE_SPECS', 'get_device_spec', 'machine_balance']

DEVICE_SPECS = {
    # historical: read in round 5, kept as the default the
    # lint thresholds were calibrated against
    'bench-r05': {
        'name': 'bench-r05',
        'peak_flops': 95.25e12,         # measured bf16 matmul peak
        'peak_int8_flops': 190.5e12,    # 2x bf16 (MXU int8 path)
        'hbm_bytes_s': 62.5e9,          # measured saxpy bandwidth
        'hbm_bytes': 16e9,
        'source': 'read in round 5 on an earlier development device '
                  '(matmul_peak_bf16_8192, hbm_bandwidth_saxpy)',
    },
    # datasheet entries
    'v5e-spec': {
        'name': 'v5e-spec',
        'peak_flops': 197e12,           # bf16
        'peak_int8_flops': 394e12,
        'hbm_bytes_s': 819e9,
        'hbm_bytes': 16e9,
        'source': 'TPU v5e datasheet',
    },
    'v4-spec': {
        'name': 'v4-spec',
        'peak_flops': 275e12,
        'peak_int8_flops': 275e12,
        'hbm_bytes_s': 1228e9,
        'hbm_bytes': 32e9,
        'source': 'TPU v4 datasheet',
    },
}

_DEFAULT = 'bench-r05'
_REQUIRED = ('peak_flops', 'hbm_bytes_s')


def get_device_spec(spec=None):
    """Resolve a device spec: a dict is passed through (validated), a
    string names a table entry or a JSON file, None reads
    ``MXNET_ANALYSIS_DEVICE_SPEC`` and falls back to the historical
    default."""
    if spec is None:
        spec = os.environ.get('MXNET_ANALYSIS_DEVICE_SPEC', _DEFAULT)
    if isinstance(spec, dict):
        resolved = dict(spec)
    elif spec in DEVICE_SPECS:
        resolved = dict(DEVICE_SPECS[spec])
    elif isinstance(spec, str) and (os.path.sep in spec
                                    or spec.endswith('.json')):
        with open(spec) as f:
            resolved = json.load(f)
        resolved.setdefault('name', os.path.basename(spec))
        resolved.setdefault('source', spec)
    else:
        raise ValueError(
            f'unknown device spec {spec!r}: want one of '
            f'{sorted(DEVICE_SPECS)}, a JSON file path, or a dict '
            '(MXNET_ANALYSIS_DEVICE_SPEC)')
    missing = [k for k in _REQUIRED if not resolved.get(k)]
    if missing:
        raise ValueError(
            f'device spec {resolved.get("name", spec)!r} missing '
            f'required key(s) {missing}: need {_REQUIRED}')
    return resolved


def machine_balance(spec):
    """Machine balance in flop/byte: the arithmetic intensity at which
    the compute and bandwidth rooflines cross."""
    return float(spec['peak_flops']) / float(spec['hbm_bytes_s'])
