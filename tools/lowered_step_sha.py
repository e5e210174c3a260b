#!/usr/bin/env python3
"""Show that a change left a benchmark cell's step program alone, without a chip.

    JAX_PLATFORMS=cpu python3 tools/lowered_step_sha.py <tree> <cell> [--compile]

Lowers ``trainer._train_step`` of ``<cell>`` (an entry of ``<tree>/BENCHMARK.json``,
at the cell's own sizes) for a DESCRIBED ``v5e:2x2`` chip with
``ops.dispatch.on_tpu`` steered, and prints the sha256 of the text with what
moves when a line of source moves taken out: each Mosaic body (the
``tpu_custom_call``'s base64 MLIR bytecode) is replaced by the sha256 of its
text printed with ``enable_debug_info=False``, and the StableHLO's own ``loc``
attributes are dropped.  Run it on the parent's unpacked tree and on the
change: equal hashes, equal programs.  ``--compile`` also compiles for the chip
and prints the memory analysis and the custom calls by kernel name.  Nothing
runs; no number of this tool is a device number.
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys
from collections import Counter

_BODY_RE = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
_CALL_RE = re.compile(r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def lowered_step(tree: str, cell_name: str):
    """The cell's ``_train_step`` of the program in ``tree``, lowered for one
    described v5e chip at the cell's sizes."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(tree)
    sys.path[:0] = [tree, os.path.join(tree, "benchmarks")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import harness
    import run as bench_run
    from tape import ensure_tape

    import gymfx_tpu.ops.dispatch as dispatch
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    dispatch.on_tpu = lambda: True      # code that asks the backend sees the CPU here
    cell = bench_run.load_cell(cell_name, False)
    config = harness.program_config(
        cell, input_data_file=str(ensure_tape(cell["traffic"]["tape"])))
    trainer = PPOTrainer(Environment(config), ppo_config_from(config))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(trainer.init_state, 0)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    return trainer._train_step.lower(args)


def without_locations(text: str) -> str:
    """The lowered text with each Mosaic body as the sha256 of its MLIR printed
    without debug locations, and without the StableHLO's ``loc`` attributes."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def body(match):
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            printed = module.operation.get_asm(enable_debug_info=False)
        return '"body": "sha256:%s"' % hashlib.sha256(printed.encode()).hexdigest()

    text = re.sub(r" loc\(.*?\)$", "", _BODY_RE.sub(body, text), flags=re.M)
    return "\n".join(line for line in text.splitlines() if not line.startswith("#loc"))


def main(argv) -> int:
    tree, cell_name = os.path.abspath(argv[1]), argv[2]
    lowered = lowered_step(tree, cell_name)
    text = lowered.as_text()
    cleaned = without_locations(text)
    print(json.dumps({
        "tree": argv[1], "cell": cell_name,
        "sha256": hashlib.sha256(cleaned.encode()).hexdigest()[:16],
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "distinct_bodies": len(set(re.findall(r"sha256:[0-9a-f]{64}", cleaned)))}))
    if "--compile" in argv:
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        names = _CALL_RE.findall(compiled.as_text())
        print(json.dumps({
            "temp_bytes": memory.temp_size_in_bytes,
            "argument_bytes": memory.argument_size_in_bytes,
            "compiled_custom_calls": len(names),
            "by_name": Counter(name.rsplit(".", 1)[0] for name in names)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
