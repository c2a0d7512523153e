"""System under test for GPT-2 configurations: ``models.gpt.GPTModel`` under
``parallel.SPMDTrainer`` (training) or ``serve.InferenceEngine`` (serving),
through their public constructors, with the benchmark's weights installed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common_mxtpu as common  # noqa: E402


def _model(c, weights, flash, remat=False):
    from incubator_mxnet_tpu.models.gpt import GPTModel
    m = GPTModel(vocab_size=c["vocab_size"], units=c["n_embd"],
                 hidden_size=c["n_inner"], num_layers=c["n_layer"],
                 num_heads=c["n_head"], max_length=c["n_positions"],
                 dropout=c["resid_pdrop"],
                 layer_norm_eps=c["layer_norm_epsilon"],
                 dtype=c["compute_dtype"], flash=flash, remat=remat)
    pairs = [("wte", None, m.word_embed.weight),
             ("wpe", None, m.position_embed.weight),
             ("lnf_g", None, m.ln_f.gamma), ("lnf_b", None, m.ln_f.beta)]
    for l in range(c["n_layer"]):
        b = getattr(m, f"block{l}")
        pairs += [("ln1_g", l, b.ln1.gamma), ("ln1_b", l, b.ln1.beta),
                  ("qkv_w", l, b.attn.qkv.weight),
                  ("qkv_b", l, b.attn.qkv.bias),
                  ("proj_w", l, b.attn.proj.weight),
                  ("proj_b", l, b.attn.proj.bias),
                  ("ln2_g", l, b.ln2.gamma), ("ln2_b", l, b.ln2.beta),
                  ("fc_w", l, b.ffn_in.weight), ("fc_b", l, b.ffn_in.bias),
                  ("out_w", l, b.ffn_out.weight),
                  ("out_b", l, b.ffn_out.bias)]
    common.install(pairs, weights)
    return m, pairs


def build_trainer(config, job, weights, n_chips, rehearsal):
    from incubator_mxnet_tpu.models.gpt import lm_loss
    model, pairs = _model(config, weights, flash=True,
                          remat=job.get("remat", False))
    return common.build_trainer(model, pairs, lm_loss, job,
                                ["input_ids", "labels"], n_chips)


class ServeSut:
    """``InferenceEngine.submit`` + ``InferenceEngine.step``: the calls that
    ``engine.run`` and the front end's pump make."""

    def __init__(self, engine, recorder):
        self.engine = engine
        self.recorder = recorder

    def request(self, prompt_ids, max_new_tokens):
        from incubator_mxnet_tpu.serve import Request
        return Request(prompt_ids=prompt_ids, max_new_tokens=max_new_tokens,
                       temperature=0.0, eos_id=-1)

    def submit(self, request):
        return self.engine.submit(request)

    def step(self):
        return self.engine.step()

    def busy(self):
        return bool(self.engine._queue) or self.engine.active_count > 0

    def ok(self, request):
        return request.outcome is not None and \
            request.outcome.name in ("EOS", "MAX_TOKENS", "STOP")

    def counters(self):
        e = self.engine
        return {"decode_steps": e.decode_steps,
                "decode_trace_count": e.decode_trace_count,
                "prefill_trace_count": sum(e.prefill_trace_counts.values()),
                "copy_trace_count": e.copy_trace_count,
                "prefix_lookups": e.prefix_lookups,
                "prefix_hits": e.prefix_hits,
                "prefix_hit_tokens": e.prefix_hit_tokens,
                "preemptions": e.preemptions}

    def events(self, kind):
        """The flight recorder's events of one kind, as dicts with ``ts``."""
        from incubator_mxnet_tpu.serve.events import EventType
        evs = self.recorder.events(etype=getattr(EventType, kind))
        return [dict(e.data, ts=e.ts, request_id=e.request_id) for e in evs]

    def close(self):
        self.engine.shutdown()
        self.engine = None


def build_engine(config, engine_cfg, weights, rehearsal):
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.events import FlightRecorder
    model, _ = _model(config, weights, flash=False)
    recorder = FlightRecorder(capacity=4_000_000, histograms=False)
    engine = serve.InferenceEngine(
        model, num_slots=engine_cfg["num_slots"],
        page_size=engine_cfg["page_size"], max_len=engine_cfg["max_len"],
        prefix_cache=engine_cfg["prefix_cache"],
        chunk_pages=engine_cfg["chunk_pages"],
        token_budget=engine_cfg.get("token_budget"),
        interpret=True if rehearsal else None, recorder=recorder)
    return ServeSut(engine, recorder)
