"""Are the serving programs of two trees the same programs? No chip needed.

    PYTHONPATH=<tree> python3 tools/aot_serving_programs.py <out-prefix> \
        [--family gpt2|granite]

compiles, with the chip's own compiler against a described ``v5e:2x2``, the
engine's decode program and its 256- and 64-position chunk-prefill programs
for two GPT-2 layers at ``gpt2xl-sessions``' widths and engine sizes (or,
with ``--family granite``, for one Mamba-2 and one attention layer of
Granite-4.0-H-Micro at ``granite4hm-chat``'s widths and engine sizes, the
whole 100,352-row table), from the tree on ``PYTHONPATH``, and writes for
each ``<out-prefix>.<program>.hlo``
(the optimized HLO in a normal form: no ``metadata={...}``, no source-location
tables, no instruction numbering, kernel bodies blanked) and
``<out-prefix>.<program>.kernels`` (the Mosaic kernels' bodies, debug
information stripped), then prints a sha256 of each. Run it from two trees
and compare the lines: equal hashes are equal programs. A tree's own paths
and line numbers live only in what the normal form drops. Some 40 s a
program on the sandbox's CPU. PERF.md (Findings) holds the hashes of the
last two trees compared, for both families.
"""

import argparse
import base64
import hashlib
import re
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


def gpt2_engine():
    """Two GPT-2-XL layers in ``gpt2xl-sessions``' engine: (engine, the
    programs' argument numbers that are donated)."""
    model = GPTModel(vocab_size=50257, units=1600, hidden_size=6400,
                     num_layers=2, num_heads=25, max_length=1024,
                     dtype="bfloat16")
    model.initialize()
    eng = InferenceEngine(model, num_slots=64, page_size=16, max_len=1024,
                          num_pages=695, prefix_cache=True, chunk_pages=16,
                          token_budget=512)
    return eng, (1,), 50257


def granite_engine():
    """One Mamba-2 and one attention layer of Granite-4.0-H-Micro in
    ``granite4hm-chat``'s engine (64 slots, pages of 16, chunks of 16
    pages; the pool cut to what two layers need)."""
    from incubator_mxnet_tpu.models.granite_hybrid import GraniteHybridModel
    model = GraniteHybridModel(
        100352, 2048, 8192, ["mamba", "attention"], 32, 8, 64, 64, 128,
        ssm_conv=4, head_dim=64, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, rms_eps=1e-5, max_length=1280,
        dtype="bfloat16", state_dtype="float32")
    model.initialize()
    eng = InferenceEngine(model, num_slots=64, page_size=16, max_len=1280,
                          num_pages=5121, prefix_cache=False, chunk_pages=16,
                          token_budget=512)
    return eng, (1, 17), 100352


def compiled_programs(family):
    """``[(tag, optimized HLO text)]`` of the family's three programs,
    compiled for one described v5e chip."""
    eng, donate, vocab = {"gpt2": gpt2_engine,
                          "granite": granite_engine}[family]()
    rng = np.random.default_rng(0)
    # on the CPU, once: leaves every program's arguments in eng._programs
    eng.run([Request(rng.integers(0, vocab, 300).astype(np.int32),
                     max_new_tokens=3, temperature=0.0)])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    pa._on_tpu = lambda: True           # the dispatchers take the kernels
    jax.clear_caches()
    out = []
    for name, fn_name in PROGRAMS:
        _, args = eng._programs[name]
        args = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), args)
        fn = jax.jit(lambda *a, _n=fn_name: getattr(eng, _n)(*a),
                     donate_argnums=donate)     # the pools (and states)
        t0 = time.time()
        text = fn.lower(*args).compile().as_text()
        tag = name if isinstance(name, str) else f"{name[0]}{name[1]}"
        print(f"{tag} compiled in {time.time() - t0:.0f} s", flush=True)
        out.append((tag, text))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("prefix")
    ap.add_argument("--family", default="gpt2", choices=("gpt2", "granite"))
    args = ap.parse_args(argv)
    for tag, text in compiled_programs(args.family):
        for kind, body in (("hlo", normal_form(text)),
                           ("kernels", kernel_bodies(text))):
            with open(f"{args.prefix}.{tag}.{kind}", "w") as f:
                f.write(body)
            print(f"{tag}.{kind} sha256 "
                  f"{hashlib.sha256(body.encode()).hexdigest()} "
                  f"({body.count(chr(10))} lines)", flush=True)


if __name__ == "__main__":
    main()
