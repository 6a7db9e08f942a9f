"""Model families: everything in the benchmark that knows a model or its data.

A configuration file names its family (``"family": "vision"``) and the
harness imports ``bench/families/<family>.py`` by that name, so a new model
is new files only. The FL algorithm (``bench/reference.py``), the costs of
aggregation (``bench/costs.py``) and the metric readers call the family for
the rest. A family module defines, each taking the configuration file's
dict ``conf``:

- ``data(conf, seed) -> (items, parts)``: the items from the seed as a dict
  of numpy arrays with a leading item axis (floating ones are cast to the
  reference's dtype), and each client's item indices;
- ``init_params(conf, seed)``: the initial parameter tree, as the program
  draws it;
- ``make_local_train(conf, total_steps, n_steps, half_batch)``: a jitted
  ``train(params, batch, lr) -> (params, mean loss)`` over ``batch``, the
  items of ``n_steps`` batches with leading axes (n_steps, batch size);
  ``half_batch`` trains on the first half of each batch (a planted fault);
- ``train_flops_per_item(conf)``: the FLOPs of training on one item;
- ``leaf_sizes(conf)``: the sizes of the parameter leaves, in the order the
  program packs them;
- ``tiny(conf)``: the configuration cut to a size the CPU tests run in
  seconds.
"""
from __future__ import annotations

import importlib
import re

_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def of(conf: dict):
    """The family module that configuration ``conf`` names."""
    name = conf.get("family")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(
            f"configuration {conf.get('name')!r} names no family: give it "
            f"\"family\": \"<name>\" for bench/families/<name>.py (got "
            f"{name!r})")
    return importlib.import_module(f"bench.families.{name}")
