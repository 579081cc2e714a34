"""What the benchmark knows of each program family, one module per family.

A role's family is ``repro.config``'s family string of its ``arch``
(``cfg.family``: ``dense``, ``moe``, ...). The harness finds the module
``benchlib.families.<family>`` by that name, as ``spec.reader`` finds a
metric's reader by the metric's name; nothing lists the families. A
module gives:

- ``weight_shapes(m) -> {leaf: (shape, fan_in or None)}``: every weight
  of model ``m`` (the configuration file's ``model`` entry), flat by
  leaf name; a fan-in of None marks a norm gain. ``weights.make_weights``
  draws them from the seed, the same way for every family.
- ``program_tree(m, w)``: the flat weights ``w`` arranged as the
  program's parameter tree for this family.
- ``served_logits(m, w, tokens, precision="float32")``: the plain
  reference's logits over the first ``reference.SERVED_VOCAB`` entries at
  every position of ``tokens`` [B, S], float32 at ``highest`` precision,
  or the float8 control with ``precision="fp8"``.
- ``layer_programs(m, batch, seq) -> {program: (calls_per_request, flops,
  bytes)}``: every layer program the program's segmentation runs for one
  request, by its segment program name, so that ``<role>/<program>`` is
  its span in the trace; FLOPs and bytes of one call, from shapes.
- ``head_flops(m, batch, seq)``: the LM head of one request.

Shared helpers stay where they are and every family imports them:
``reference`` (``SERVED_VOCAB``, ``token_gaps``, ``_q8``, ``_mm``,
``_rms``, ``_rope``), ``flops`` (``_attended_keys``, ``least_time``,
``head_flops``) and ``weights`` (``seed32``, ``head_dim``).

A configuration of a new family is: its module here,
``bench/benchlib/families/<family>.py``; its
``bench/configs/<name>.json``; its ``bench/traffic/<name>.json``; and
its entries in ``BENCHMARK.json``. No file that is already there
changes.
"""
from __future__ import annotations

import importlib


def find(family: str):
    """The module of program family ``family``; a ``ValueError`` that
    names the file to add where there is none."""
    name = f"{__name__}.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(
            f"the benchmark has no module for program family {family!r}: "
            f"add bench/benchlib/families/{family}.py "
            f"(bench/benchlib/families/__init__.py says what it "
            f"gives)") from None
