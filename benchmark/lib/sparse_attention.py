"""What the readers of the learned-sparse-attention metrics share: the
decode segment's device operations sorted into the indexer's kernel, the
selection and the attention over the chosen rows, and the bytes each has to
read at the least.

The operations are recognised as follows, inside the ``jit_segment`` runs
that a traced ``engine.segment`` span dispatched (``lib/host_spans.py``):

- the indexer: the Pallas kernel named ``dsa_index_scores``;
- the selection: every other operation whose result's first array type is
  ``[rows, positions]`` (``rows`` = the engine's ``max_batch``, ``positions``
  = ``max_pages x page_size``): the masking of the scores by the lengths and
  ``lax.top_k``, which the chip's compiler turns into a ``sort`` of that
  shape;
- the attention over the chosen rows: every other operation whose result
  holds the chosen positions as a dimension, ``[rows, chosen, *]`` or
  ``[rows, *, chosen]`` or ``[rows, chosen]`` (``chosen`` = ``index_topk``):
  the flat indices, XLA's gather of the chosen rows, the scores over them,
  the softmax and the probabilities. The two products with ``Wkvb`` (the
  absorbed query, the output's value half) and the product of the
  probabilities with the rows give ``[rows, heads, 512 or 128]`` and are not
  told apart by shape: none of the three is counted, so the time errs low
  by one small product and the share of the roofline high by as much.
"""
import bisect
import re

from benchmark.lib import host_spans as hs
from benchmark.lib import trace_reduce as tr

MODULE = "jit_segment"
SPAN = "engine.segment"
INDEX_KERNEL = "dsa_index_scores"
_DIMS = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def geometry(ctx) -> dict:
    cfg, eng = ctx["config"], ctx["mix"]["engine"]
    positions = eng["max_pages"] * eng["page_size"]
    return {"rows": eng["max_batch"], "positions": positions,
            "chosen": min(cfg["index_topk"], positions)}


def result_dims(event):
    """Dimensions of the first array type of an operation's result."""
    head = event[0].split(" = ", 1)
    m = _DIMS.search(head[1]) if len(head) == 2 else None
    return [int(d) for d in m.group(1).split(",") if d] if m else []


def kind(event, geo) -> str:
    """"index", "selection", "attention" or "" for a device operation."""
    if tr.is_pallas(event):
        return ("index" if tr.op_name(event).startswith(INDEX_KERNEL)
                else "")
    dims = result_dims(event)
    if not dims or dims[0] != geo["rows"]:
        return ""
    if dims == [geo["rows"], geo["positions"]]:
        return "selection"
    if geo["chosen"] in dims[1:]:
        return "attention"
    return ""


def segment_times(ctx):
    """(matched runs, {kind: self ns}, self ns of every operation) inside
    the ``jit_segment`` runs that a traced ``engine.segment`` span with the
    sparse-attention counter dispatched; (None, None, None) without one."""
    runs = [r for r in hs.segment_runs(ctx, MODULE, SPAN)
            if "ctx_tokens_selected" in r[2]]
    if not runs:
        return None, None, None
    geo = geometry(ctx)
    starts = [r[0] for r in runs]
    ops = tr.line_events(tr.device_planes(ctx["raw"])[0], tr.OPS_LINE)
    by, whole = {"index": 0, "selection": 0, "attention": 0}, 0
    for ev, self_ns in zip(ops, tr.self_times(ops)):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0 or ev[1] >= runs[i][1]:
            continue
        whole += self_ns
        k = kind(ev, geo)
        if k:
            by[k] += self_ns
    return runs, by, whole


def hbm_bytes_per_s(ctx) -> float:
    peaks = ctx.get("peaks")
    if peaks is None:
        import jax

        from benchmark.lib.peaks import peaks as peaks_of

        peaks = peaks_of(jax.devices()[0].device_kind)
    return peaks["hbm_bytes_per_s"]


def dtype_bytes(config: dict) -> int:
    import jax.numpy as jnp

    return jnp.dtype(config["dtype"]).itemsize


def index_key_bytes(config: dict, a: dict) -> int:
    """Indexer keys a segment has to read at the least: every layer and
    step, each live row's whole context (``ctx_tokens`` at the segment's
    start, one more token a row a step), ``index_head_dim`` wide."""
    steps = a["steps"]
    tokens = steps * a["ctx_tokens"] + a["rows"] * steps * (steps - 1) // 2
    return (config["num_hidden_layers"] * tokens * config["index_head_dim"]
            * dtype_bytes(config))


def chosen_row_bytes(config: dict, a: dict) -> int:
    """Cache rows a segment's attention has to read at the least: every
    layer, the chosen positions of every live row and step
    (``ctx_tokens_selected``, summed over the segment's steps inside its
    program), ``kv_lora_rank + qk_rope_head_dim`` wide."""
    return (config["num_hidden_layers"] * a["ctx_tokens_selected"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * dtype_bytes(config))
