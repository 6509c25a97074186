"""The models the benchmark serves, one module per published ``model_type``.

A configuration file (``bench/configs/<name>.json``) carries the published
config's ``model_type`` key; `for_spec` imports ``bench.models.<model_type>``
from it.  Everything that knows a model's layers lives in that module, so a
new model comes in as new files: its configuration, its module, its mix,
its metric files and its entries in ``BENCHMARK.json``.  A module gives:

* ``program_config(spec)``: the program's ``ModelConfig`` for the
  configuration (the one place that imports the program);
* ``PROGRAM_PATHS``: each path of the program's parameter tree -> the
  published weight it holds;
* ``weight_shapes(spec)``: published weight name -> ``(shape, kind)``, the
  table `bench.weights` draws from (``kind`` picks the scale,
  `bench.weights.SCALE`); a tensor's key is its index in the sorted names,
  so renaming or adding a weight changes every seed's draw;
* ``served_gap(weights, spec, prompt, served, *, controls=())``: the plain
  reference's check of served tokens (`bench.harness.check`), with the
  lower-precision controls that ``controls`` names.

The work counts in ``bench/counts.py`` and the two metrics that read them
(``decode_hbm_share.serve``, ``step_mfu.prefill``) are the dense decoder's,
and their ``workloads`` list only its cells.  A model whose kernels do other
work brings its own count functions in its module and metric files of its
own that read them.
"""

from __future__ import annotations

import importlib
import pkgutil
import types


def known() -> list[str]:
    """The ``model_type`` of every model module here."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def for_spec(spec: dict) -> types.ModuleType:
    """The model module that the configuration's ``model_type`` names."""
    model_type = spec.get("model_type")
    if isinstance(model_type, str) and model_type.isidentifier():
        name = f"{__name__}.{model_type}"
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:
                raise
    raise KeyError(f"configuration {spec.get('name')!r} has model_type "
                   f"{model_type!r}, which no bench/models module serves; "
                   f"known: {known()}")
