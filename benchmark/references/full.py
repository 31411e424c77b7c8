"""full-1m-pct5's own guarantees, beside the base replay's twelve: the
union of the three single-family references beside this file, each held
bind by bind on the same watch history and the same pattern.

- ``selector_mismatch``, ``taint_untolerated`` (``affinity.py``):
  NodeAffinity's and TaintToleration's filters;
- ``zone_skew_exceeded`` (``spread.py``): the hard zone constraints'
  ``maxSkew`` at every bind, in watch order;
- ``anti_affinity_violated``, ``affinity_unmet`` (``interpod.py``):
  required anti-affinity and its symmetry, required affinity outside the
  bootstrap.

Each guarantee has one implementation: the files are loaded by path from
this file's own directory, as the harness loads a reference, and each
reads only the keys of a shape that are its own.  Imports nothing of the
program or the harness.
"""

import importlib.util
import os

PARTS = ("affinity", "spread", "interpod")


def _numbers_of(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"full_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.numbers


def numbers(seen, replayed, *, nodes, pattern, offered):
    out = {}
    for name in PARTS:
        out.update(_numbers_of(name)(
            seen, replayed, nodes=nodes, pattern=pattern, offered=offered))
    return out
