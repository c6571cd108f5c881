"""sha256 of the lowered text of the serving engine's programs, for one fixed
tiny dense model, one tiny sparse-expert, window-attention model, one tiny
latent-attention model with the sparse-attention indexer, one tiny model of
linear-attention layers that keep a state a row, one tiny sparse-expert,
window-attention model whose router reads the attention's input and one
tiny model of latent attention beside grouped linear-attention layers.

A change to the engine is held to this: a refactor must leave every column
as it was, and a change of a program's text must move the programs it names
and no other (`PERF.md` section 6, PRs 30 and 31). Lowering traces and
never compiles, so it runs on the CPU in seconds:

    JAX_PLATFORMS=cpu python experiments/exp_program_hashes.py
    JAX_PLATFORMS=cpu python experiments/exp_program_hashes.py --against PARENT

With ``--against`` the checkout at PARENT runs ITS OWN copy of this script
(the programs' arguments belong to the commit) and both columns are printed
side by side.
"""
import hashlib
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.generation import \
    PagedContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import LlamaForCausalLM, llama_config  # noqa: E402
from paddle_tpu.models.afmoe import (AfmoeConfig,  # noqa: E402
                                     AfmoeForCausalLM)
from paddle_tpu.models.deepseek_v32 import (  # noqa: E402
    DeepseekV32Config, DeepseekV32ForCausalLM)
from paddle_tpu.models.gigachat35 import (  # noqa: E402
    GigaChat35Config, GigaChat35ForCausalLM)
from paddle_tpu.models.olmo_hybrid import (  # noqa: E402
    OlmoHybridConfig, OlmoHybridForCausalLM)
from paddle_tpu.models.smallthinker import (  # noqa: E402
    SmallThinkerConfig, SmallThinkerForCausalLM)

STEPS, WIDTH, CHUNK, DRAFT_K = 4, 16, 8, 3


def sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def dense_model():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_config(
        "tiny", num_hidden_layers=2, num_key_value_heads=2))
    model.eval()
    return model


def sparse_model():
    paddle.seed(3)
    model = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=16, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention", "full_attention"]))
    model.eval()
    return model


def latent_model():
    paddle.seed(3)
    model = DeepseekV32ForCausalLM(DeepseekV32Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=16, index_head_dim=16, index_topk=8,
        first_k_dense_replace=1, n_routed_experts=16, ep_size=4, ep_rank=1,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 16}))
    model.eval()
    return model


def hybrid_model():
    paddle.seed(3)
    model = OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        linear_num_key_heads=3, linear_num_value_heads=3,
        linear_key_head_dim=8, linear_value_head_dim=16))
    model.eval()
    return model


def smallthinker_model():
    paddle.seed(3)
    model = SmallThinkerForCausalLM(SmallThinkerConfig(
        vocab_size=256, hidden_size=64, moe_ffn_hidden_size=32,
        num_hidden_layers=4, num_attention_heads=7, num_key_value_heads=1,
        head_dim=16, sliding_window_size=16, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3))
    model.eval()
    return model


def latent_hybrid_model():
    paddle.seed(3)
    model = GigaChat35ForCausalLM(GigaChat35Config(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        qk_head_dim=24, v_head_dim=16, n_routed_experts=16, ep_size=4,
        num_experts_per_tok=4, first_k_dense_replace=1,
        full_attention_layers=[3], linear_key_head_dim=16,
        linear_value_head_dim=8, linear_num_key_heads=2,
        linear_num_value_heads=4))
    model.eval()
    return model


def programs(eng):
    """(name, lowered) of each program the engine can run, with the
    arguments the engine itself passes."""
    mb = eng.max_batch
    idle = np.zeros((mb,), bool)
    key = (np.uint32(0), np.uint32(0))
    pools, pt = eng.caches
    prefill = eng._prefill_paged._jitted.lower(
        eng.params, np.zeros((1, WIDTH), np.int32), pools, pt, np.int32(0),
        np.int32(WIDTH), eng._bank(), np.int32(0))
    yield "jit_prefill_one", prefill
    yield "jit_segment", eng._segment_fn(STEPS)._jitted.lower(
        eng.params, eng.last, eng.lens, eng.done_dev, idle, eng.samp,
        eng._bank(), eng.caches, *key)
    yield "cb_admit_state", eng._admit_state._jitted.lower(
        eng.lens, eng.last, eng.done_dev, eng.samp, eng.hist, eng.hist_len,
        np.int32(0), np.int32(5), prefill.out_info[0], np.uint32(0),
        np.float32(1.0), np.int32(0), np.float32(1.0), np.bool_(False),
        np.int32(-1), np.int32(0), np.int32(0), np.int32(0),
        np.zeros((eng.spec_history,), np.int32), np.int32(0))
    if eng.prefill_chunk is not None:
        yield "cb_prefill_chunk", eng._prefill_chunk._jitted.lower(
            eng.params, np.zeros((1, eng.prefill_chunk), np.int32),
            eng._mini_cache(eng.max_len), np.int32(0), np.int32(0),
            eng._bank(), np.int32(0))
    if eng.draft_k and eng.spec_mode == "host":
        yield "cb_spec_step", eng._spec_step_fn()._jitted.lower(
            eng.params, eng.last, eng.lens, idle, eng.samp, eng._bank(),
            eng.caches, *key, np.zeros((mb, eng.draft_k), np.int32), idle,
            np.zeros((mb,), np.int32))
    if eng.draft_k and eng.spec_mode == "device":
        yield ("cb_spec_device_segment",
               eng._spec_segment_device_fn(STEPS)._jitted.lower(
                   eng.params, eng.last, eng.lens, eng.done_dev, idle,
                   eng.samp, eng._bank(), eng.caches, eng.hist,
                   eng.hist_len, np.zeros((mb,), np.int32),
                   np.zeros((mb,), np.int32), *key))


def rows():
    """(engine tag, program name, hash) of every program of every engine."""
    geometry = dict(max_batch=2, num_pages=16, page_size=8, max_pages=8,
                    prefill_buckets=[WIDTH, 64])
    engines = [
        ("dense", dense_model, dict(prefill_chunk=CHUNK)),
        ("dense int8+lora", dense_model,
         dict(kv_dtype="int8", lora_capacity=2, lora_rank=2)),
        ("dense spec host", dense_model, dict(draft_k=DRAFT_K)),
        ("dense spec device", dense_model,
         dict(draft_k=DRAFT_K, spec_mode="device")),
        ("afmoe", sparse_model, dict(num_pages=64, page_size=4,
                                     max_pages=16)),
        ("deepseek_v32", latent_model, dict(num_pages=64, page_size=4,
                                            max_pages=16)),
        ("olmo_hybrid", hybrid_model, dict(num_pages=64, page_size=4,
                                           max_pages=16)),
        ("smallthinker", smallthinker_model, dict(num_pages=64, page_size=4,
                                                  max_pages=16)),
        ("gigachat35", latent_hybrid_model, dict(num_pages=64, page_size=4,
                                                 max_pages=16)),
    ]
    for tag, make, kw in engines:
        eng = PagedContinuousBatchingEngine(make(), **{**geometry, **kw})
        for name, lowered in programs(eng):
            yield tag, name, sha(lowered)
        eng.close()


def parent_rows(root):
    """The same table from the checkout at ``root``, by its own script."""
    out = subprocess.run(
        [sys.executable, os.path.join(root, "experiments",
                                      "exp_program_hashes.py")],
        check=True, capture_output=True, text=True).stdout
    table = {}
    for line in out.splitlines():
        # "<tag padded to 18> <name> <hash>": the tag may hold spaces
        tag, (name, digest) = line[:18].strip(), line[18:].split()
        table[tag, name] = digest
    return table


def main():
    against = None
    if "--against" in sys.argv:
        against = parent_rows(sys.argv[sys.argv.index("--against") + 1])
    for tag, name, digest in rows():
        line = f"{tag:18s} {name:24s} {digest}"
        if against is not None:
            was = against.get((tag, name), "-")
            verdict = ("same" if was == digest else
                       "new" if was == "-" else "CHANGED")
            line = f"{tag:18s} {name:24s} {was:16s} {digest} {verdict}"
        print(line)


if __name__ == "__main__":
    main()
