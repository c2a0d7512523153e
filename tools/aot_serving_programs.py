"""Are the serving programs of two trees the same programs? No chip needed.

    PYTHONPATH=<tree> python3 tools/aot_serving_programs.py <out-prefix>

compiles, with the chip's own compiler against a described ``v5e:2x2``, the
engine's decode program and its 256- and 64-position chunk-prefill programs
for two GPT-2 layers at ``gpt2xl-sessions``' widths and engine sizes, from
the tree on ``PYTHONPATH``, and writes for each ``<out-prefix>.<program>.hlo``
(the optimized HLO in a normal form: no ``metadata={...}``, no source-location
tables, no instruction numbering, kernel bodies blanked) and
``<out-prefix>.<program>.kernels`` (the Mosaic kernels' bodies, debug
information stripped), then prints a sha256 of each. Run it from two trees
and compare the lines: equal hashes are equal programs. A tree's own paths
and line numbers live only in what the normal form drops. Some 40 s a
program on the sandbox's CPU. PERF.md (Findings, PR 36) holds the hashes
that the parent of PR 36 and PR 36 gave.
"""

import base64
import hashlib
import re
import sys
import time

import numpy as np

import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.ops import pallas_attention as pa
from incubator_mxnet_tpu.serve import InferenceEngine, Request

PROGRAMS = (("decode", "_decode_step_fn"),
            (("chunk", 256), "_chunk_prefill_fn"),
            (("chunk", 64), "_chunk_prefill_fn"))


def normal_form(text):
    text = re.sub(r', metadata=\{[^}]*\}', '', text)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    lines = [re.sub(r'\.\d+', '', line) for line in text.splitlines()
             if not re.match(r'^\d+ ', line)         # source-location tables
             and not re.match(r'^(FileNames|FunctionNames|FileLocations|'
                              r'StackFrames)', line.strip())]
    return "\n".join(lines) + "\n"


def kernel_bodies(text):
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    out = []
    for m in re.finditer(r'"body":"([^"]*)"', text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            out.append(mod.operation.get_asm(enable_debug_info=False))
    return "\n=====\n".join(out) + "\n"


def main(prefix):
    model = GPTModel(vocab_size=50257, units=1600, hidden_size=6400,
                     num_layers=2, num_heads=25, max_length=1024,
                     dtype="bfloat16")
    model.initialize()
    eng = InferenceEngine(model, num_slots=64, page_size=16, max_len=1024,
                          num_pages=695, prefix_cache=True, chunk_pages=16,
                          token_budget=512)
    rng = np.random.default_rng(0)
    # on the CPU, once: leaves every program's arguments in eng._programs
    eng.run([Request(rng.integers(0, 50257, 300).astype(np.int32),
                     max_new_tokens=3, temperature=0.0)])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    pa._on_tpu = lambda: True           # the dispatchers take the kernels
    jax.clear_caches()
    for name, fn_name in PROGRAMS:
        _, args = eng._programs[name]
        args = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), args)
        fn = jax.jit(lambda *a, _n=fn_name: getattr(eng, _n)(*a),
                     donate_argnums=(1,))       # the pools
        t0 = time.time()
        text = fn.lower(*args).compile().as_text()
        tag = name if isinstance(name, str) else f"{name[0]}{name[1]}"
        for kind, body in (("hlo", normal_form(text)),
                           ("kernels", kernel_bodies(text))):
            with open(f"{prefix}.{tag}.{kind}", "w") as f:
                f.write(body)
            print(f"{tag}.{kind} sha256 "
                  f"{hashlib.sha256(body.encode()).hexdigest()} "
                  f"({body.count(chr(10))} lines)", flush=True)
        print(f"{tag} compiled in {time.time() - t0:.0f} s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
