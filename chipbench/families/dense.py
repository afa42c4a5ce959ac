"""Dense GQA decoders of the Qwen2 / Llama family (``"family": "dense"``
in a configuration file): the weights the benchmark draws
(``weights.py``), the plain reference that judges them
(``reference.py``), and how both map onto the program under test.

A family file gives ``make_weights(cfg, seed)``, ``logit_gaps`` (as
``reference.logit_gaps``), ``model(cfg)`` (the program's model, kernels
on) and ``program_params(weights)`` (the same device buffers in the
program's parameter tree).
"""

import reference
import weights

make_weights = weights.make
logit_gaps = reference.logit_gaps


def model(cfg: dict):
    from repro.models import build_model
    from repro.models.config import ModelConfig
    if cfg["tie_word_embeddings"]:
        raise ValueError("tied embeddings are not drawn by weights.py")
    return build_model(ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["attention_bias"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        param_dtype=cfg["torch_dtype"], tie_embeddings=False),
        remat=False, use_kernels=True)


def program_params(w: dict) -> dict:
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    block = {"ln1": {"scale": w["ln1"]}, "attn": attn,
             "ln2": {"scale": w["ln2"]},
             "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "blocks": {"sub0": block},
            "ln_f": {"scale": w["ln_f"]}, "head": w["head"]}
