"""BERT pre-training through ``ParallelEngine``, as ``bench.py`` and
``chip_smoke.py`` build it, with the masked positions gathered before the
MLM head when the cell's traffic carries them."""

from . import load_weights, make_optimizer

_LAYER = {"self_attn.q_proj.weight": "wq", "self_attn.q_proj.bias": "bq",
          "self_attn.k_proj.weight": "wk", "self_attn.k_proj.bias": "bk",
          "self_attn.v_proj.weight": "wv", "self_attn.v_proj.bias": "bv",
          "self_attn.out_proj.weight": "wo", "self_attn.out_proj.bias": "bo",
          "linear1.weight": "w1", "linear1.bias": "b1",
          "linear2.weight": "w2", "linear2.bias": "b2",
          "norm1.weight": "ln1_g", "norm1.bias": "ln1_b",
          "norm2.weight": "ln2_g", "norm2.bias": "ln2_b"}
_TOP = {"bert.embeddings.word_embeddings.weight": "word_emb",
        "bert.embeddings.position_embeddings.weight": "pos_emb",
        "bert.embeddings.token_type_embeddings.weight": "type_emb",
        "bert.embeddings.layer_norm.weight": "emb_ln_g",
        "bert.embeddings.layer_norm.bias": "emb_ln_b",
        "bert.pooler.dense.weight": "pool_w",
        "bert.pooler.dense.bias": "pool_b",
        "cls.decoder_weight": "word_emb",       # tied: the same Tensor
        "cls.decoder_bias": "mlm_bias",
        "cls.transform.weight": "mlm_w", "cls.transform.bias": "mlm_b",
        "cls.layer_norm.weight": "mlm_ln_g",
        "cls.layer_norm.bias": "mlm_ln_b",
        "seq_relationship.weight": "nsp_w",
        "seq_relationship.bias": "nsp_b"}


def leaves(cfg):
    out = [(p, r, None) for p, r in _TOP.items()]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"bert.encoder.layers.{i}.{p}", r, i)
                for p, r in _LAYER.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (BertForPretraining, BertModel,
                                         BertPretrainingCriterion)
    model = BertForPretraining(BertModel(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_act=cfg["hidden_act"],
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        initializer_range=cfg["initializer_range"]))
    load_weights(model, weights)
    crit = BertPretrainingCriterion(cfg["vocab_size"])
    opt, first_grad = make_optimizer(paddle, cfg["optimizer"],
                                     model.parameters())

    def loss_fn(m, b):
        pos = Tensor(b["mlm_pos"]) if "mlm_pos" in b else None
        scores, rel = m(Tensor(b["ids"]), masked_positions=pos)
        return crit(scores, rel, Tensor(b["mlm_labels"]), Tensor(b["nsp"]))

    engine = ParallelEngine(
        model, opt, loss_fn,
        mesh=build_mesh(dp=len(devices), devices=list(devices)),
        amp_dtype=cfg["precision"]["compute"]
        if cfg["precision"]["compute"] != "float32" else None)
    return {"engine": engine, "model": model, "first_grad": first_grad}
