"""Transformer building-block layers: rms_norm, rope, multihead attention
(flash/ring kernel dispatch), silu. These extend the fluid layer surface
the way its fused contrib ops did, but TPU-native."""
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .. import initializer as init_mod

__all__ = ["rms_norm", "rope", "multihead_attention", "silu", "moe_ffn",
           "llama_decoder_stack", "llama_generate",
           "llama_spec_generate", "llama_paged_prefill",
           "llama_paged_prefill_chunk",
           "llama_paged_decode", "llama_paged_spec_step",
           "block_paged_op",
           "fused_head_cross_entropy", "llama_stack_1f1b_loss"]


def fused_head_cross_entropy(h, label, vocab_size, chunk_size=8192,
                             ignore_index=-100, head_name="lm_head",
                             name=None):
    """Per-token ``softmax_with_cross_entropy(h @ lm_head, label)``
    WITHOUT materializing the [tokens, vocab] logits — vocab-chunked
    online logsumexp with a chunk-recomputing backward (see
    ops/fused_loss.py). h: [..., D]; label: [...] or [..., 1] int.
    Creates (or reuses) the ``head_name`` parameter [D, vocab] so
    generation and checkpointing see the ordinary lm_head weight."""
    helper = LayerHelper("fused_head_cross_entropy", name=name)
    d = int(h.shape[-1])
    head = helper.create_parameter(
        ParamAttr(name=head_name,
                  initializer=init_mod.Normal(0.0, 0.02)),
        [d, vocab_size], h.dtype)
    lead = list(h.shape[:-1])
    loss = helper.create_variable_for_type_inference(
        "float32", shape=lead + [1])
    helper.append_op(
        type="fused_head_cross_entropy",
        inputs={"X": [h.name], "W": [head.name], "Label": [label.name]},
        outputs={"Loss": [loss.name]},
        attrs={"chunk_size": chunk_size, "ignore_index": ignore_index})
    return loss


def _stack_params(helper, x_dtype, n_layers, n_heads, n_kv_heads, d, hd,
                  ffn_hidden, param_attr, pp_sharded=True,
                  include_ffn=True):
    """The layer-stacked decoder weights (leading [L] axis), named
    ``{helper.name}.{suffix}`` — shared by llama_decoder_stack
    (training) and llama_generate (inference) so a trained scope
    serves generation directly."""
    from jax.sharding import PartitionSpec as P
    import copy
    base_attr = ParamAttr._to_attr(param_attr)

    def _p(suffix, shape, default_init):
        attr = copy.copy(base_attr) if base_attr else ParamAttr()
        attr.name = f"{helper.name}.{suffix}"
        if attr.initializer is None:
            attr.initializer = default_init
        w = helper.create_parameter(attr, shape, x_dtype)
        if pp_sharded:
            w.sharding = P(*(("pp",) + (None,) * (len(shape) - 1)))
        return w

    ninit = init_mod.Normal(0.0, 0.02)
    L = n_layers
    out = {
        "AttnNorm": _p("attn_norm", [L, d], init_mod.Constant(1.0)),
        "Wq": _p("wq", [L, d, n_heads * hd], ninit),
        "Wk": _p("wk", [L, d, n_kv_heads * hd], ninit),
        "Wv": _p("wv", [L, d, n_kv_heads * hd], ninit),
        "Wo": _p("wo", [L, n_heads * hd, d], ninit),
        "MlpNorm": _p("mlp_norm", [L, d], init_mod.Constant(1.0)),
    }
    if include_ffn:
        out["WGate"] = _p("w_gate", [L, d, ffn_hidden], ninit)
        out["WUp"] = _p("w_up", [L, d, ffn_hidden], ninit)
        out["WDown"] = _p("w_down", [L, ffn_hidden, d], ninit)
    return out


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    d = int(input.shape[-1])
    scale = helper.create_parameter(helper.param_attr, [d], input.dtype,
                                    default_initializer=init_mod.Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="rms_norm",
                     inputs={"X": [input.name], "Scale": [scale.name]},
                     outputs={"Y": [out.name]},
                     attrs={"epsilon": epsilon})
    return out


def rope(x, base=10000.0, name=None):
    """x: [batch, seq, heads, head_dim]."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="rope", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"base": base})
    return out


def multihead_attention(q, k, v, causal=True, scale=None, name=None):
    """q,k,v: [batch, seq, heads, head_dim] (k/v may have fewer heads for
    GQA). Lowers to the Pallas flash kernel, or ring attention when the
    active mesh has an 'sp' axis."""
    helper = LayerHelper("multihead_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    attrs = {"causal": causal}
    if scale is not None:
        attrs["scale"] = scale
    helper.append_op(type="multihead_attention",
                     inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def moe_ffn(x, num_experts, hidden_dim, top_k=2, capacity_factor=2.0,
            param_attr=None, name=None):
    """Mixture-of-Experts SwiGLU FFN (GShard/Switch recipe, TPU-first).

    x: [batch, seq, dim]. Expert weights are created [E, dim, hidden] /
    [E, hidden, dim] so the sharding transpiler (or a manual
    ``var.sharding = P('ep', ...)``) can split them over the mesh 'ep'
    axis; the op's sharding constraints then make GSPMD route tokens
    with an all_to_all over ICI. Returns (out [batch, seq, dim],
    aux_loss scalar) — add ``aux_weight * aux_loss`` to the training
    loss for load balancing.
    """
    from jax.sharding import PartitionSpec as P
    helper = LayerHelper("moe_ffn", param_attr=param_attr, name=name)
    d = int(x.shape[-1])
    base = ParamAttr._to_attr(param_attr)

    def _p(suffix, shape):
        # honor the caller's param_attr (initializer/regularizer/...)
        # with a per-weight name; default init is Normal(0, 0.02)
        import copy
        attr = copy.copy(base) if base else ParamAttr()
        attr.name = f"{helper.name}.{suffix}"
        if attr.initializer is None:
            attr.initializer = init_mod.Normal(0.0, 0.02)
        return helper.create_parameter(attr, shape, x.dtype)

    gate_w = _p("router", [d, num_experts])
    w_up = _p("w_up", [num_experts, d, hidden_dim])
    w_gate = _p("w_gate", [num_experts, d, hidden_dim])
    w_down = _p("w_down", [num_experts, hidden_dim, d])
    for w in (w_up, w_gate, w_down):
        w.sharding = P("ep", None, None)

    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    aux = helper.create_variable_for_type_inference("float32", shape=[])
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [x.name], "GateW": [gate_w.name], "WUp": [w_up.name],
                "WGate": [w_gate.name], "WDown": [w_down.name]},
        outputs={"Out": [out.name], "AuxLoss": [aux.name]},
        attrs={"top_k": top_k, "capacity_factor": capacity_factor})
    return out, aux


def llama_decoder_stack(x, n_layers, n_heads, n_kv_heads, ffn_hidden,
                        rope_base=10000.0, epsilon=1e-6, n_micro=0,
                        remat=True, scan_unroll=1, param_attr=None,
                        name=None):
    """The full decoder-layer stack as one op with layer-stacked weights
    (leading [L] axis) — see ops/transformer_ops.py for the lowering.

    x: [batch, seq, dim]. Weights are created stacked and annotated
    ``P('pp', ...)`` so a mesh with a 'pp' axis shards stages across
    devices and the op runs the GPipe microbatch schedule; on a mesh
    without 'pp' the same program scans over layers on every device.
    ``n_micro``: microbatches for the pipeline schedule (0 → one per
    stage). Returns [batch, seq, dim].
    """
    helper = LayerHelper("llama_decoder_stack", param_attr=param_attr,
                         name=name)
    d = int(x.shape[-1])
    hd = d // n_heads
    weights = _stack_params(helper, x.dtype, n_layers, n_heads,
                            n_kv_heads, d, hd, ffn_hidden, param_attr)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="llama_decoder_stack",
        inputs={"X": [x.name],
                **{slot: [w.name] for slot, w in weights.items()}},
        outputs={"Out": [out.name]},
        attrs={"n_heads": n_heads, "n_kv_heads": n_kv_heads,
               "rope_base": rope_base, "epsilon": epsilon,
               "n_micro": n_micro, "remat": remat,
               "scan_unroll": int(scan_unroll)})
    return out


def llama_stack_1f1b_loss(x, targets, vocab_size, n_layers, n_heads,
                          n_kv_heads, ffn_hidden, rope_base=10000.0,
                          epsilon=1e-6, n_micro=0, remat=True,
                          loss_chunk=8192, scan_unroll=1,
                          param_attr=None, name=None,
                          final_norm_name="final_norm",
                          head_name="lm_head"):
    """Decoder stack + final norm + lm head + cross entropy as ONE
    loss-valued op so the 1F1B schedule can interleave backward inside
    forward on a 'pp' mesh (see ops/transformer_ops.py). Creates the
    same parameter names as llama_decoder_stack + build_llama's head,
    so checkpoints and the generator interoperate. Returns the scalar
    mean loss."""
    helper = LayerHelper("llama_stack_1f1b_loss", param_attr=param_attr,
                         name=name)
    d = int(x.shape[-1])
    hd = d // n_heads
    weights = _stack_params(helper, x.dtype, n_layers, n_heads,
                            n_kv_heads, d, hd, ffn_hidden, param_attr)
    fnorm = helper.create_parameter(
        ParamAttr(name=final_norm_name,
                  initializer=init_mod.Constant(1.0)), [d], x.dtype)
    head = helper.create_parameter(
        ParamAttr(name=head_name,
                  initializer=init_mod.Normal(0.0, 0.02)),
        [d, vocab_size], x.dtype)
    loss = helper.create_variable_for_type_inference("float32", shape=[])
    helper.append_op(
        type="llama_stack_1f1b_loss",
        inputs={"X": [x.name], "Targets": [targets.name],
                "FinalNorm": [fnorm.name], "LmHead": [head.name],
                **{slot: [w.name] for slot, w in weights.items()}},
        outputs={"Loss": [loss.name]},
        attrs={"n_heads": n_heads, "n_kv_heads": n_kv_heads,
               "rope_base": rope_base, "epsilon": epsilon,
               "n_micro": n_micro, "remat": remat,
               "loss_chunk": loss_chunk,
               "scan_unroll": int(scan_unroll)})
    return loss


def _validate_sampling(temperature, top_k, top_p):
    """Eager (program-build-time) twin of warp_logits' guards: a bad
    processor config must fail when the generator is BUILT, not when
    the program is first traced."""
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def llama_generate(tokens, vocab_size, dim, n_layers, n_heads,
                   n_kv_heads, ffn_hidden, max_new_tokens,
                   rope_base=10000.0, epsilon=1e-6, dtype="float32",
                   temperature=0.0, top_k=0, top_p=1.0,
                   name="blocks", emb_name="tok_emb",
                   final_norm_name="final_norm", head_name="lm_head",
                   quantize=False, eos_id=None, pad_id=0,
                   moe_experts=0, moe_top_k=2,
                   unroll_layers=False, decode_unroll=1,
                   kv_int8=False, return_probs=False):
    """Greedy KV-cache generation as one op (see ops/transformer_ops.py
    llama_generate): prefill + decode scan fused into a single XLA
    program. Parameter names default to the ones ``build_llama``
    creates (tok_emb / {name}.* / final_norm / lm_head), so running
    this program against a trained scope generates from the trained
    weights. tokens: [batch, prompt_len] int; returns
    [batch, prompt_len + max_new_tokens].

    ``quantize=True`` builds the weight-only int8 serving form: the
    stacked matmul weights and lm head are declared int8 with
    ``<w>@scale`` per-output-channel companions (write them with
    models.llama.quantize_generator_weights on a trained scope) and
    dequantization fuses into each matmul inside the decode scan —
    int8 stays resident in HBM, halving the weight traffic decode is
    bound by."""
    _validate_sampling(temperature, top_k, top_p)
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    helper = LayerHelper("llama_generate", name=name)
    hd = dim // n_heads
    weights = _stack_params(helper, dtype, n_layers, n_heads,
                            n_kv_heads, dim, hd, ffn_hidden, None,
                            pp_sharded=False,
                            include_ffn=moe_experts == 0)
    moe_inputs = {}
    if moe_experts:
        ninit = init_mod.Normal(0.0, 0.02)
        E, L = moe_experts, n_layers
        def _mp(suffix, shape):
            return helper.create_parameter(
                ParamAttr(name=f"{helper.name}.{suffix}",
                          initializer=ninit), shape, dtype)
        moe_inputs = {
            "MoeRouter": [_mp("moe_router", [L, dim, E]).name],
            "MoeWGate": [_mp("moe_w_gate", [L, E, dim, ffn_hidden]).name],
            "MoeWUp": [_mp("moe_w_up", [L, E, dim, ffn_hidden]).name],
            "MoeWDown": [_mp("moe_w_down", [L, E, ffn_hidden, dim]).name],
        }
    emb = helper.create_parameter(
        ParamAttr(name=emb_name,
                  initializer=init_mod.Normal(0.0, 0.02)),
        [vocab_size, dim], dtype)
    fnorm = helper.create_parameter(
        ParamAttr(name=final_norm_name,
                  initializer=init_mod.Constant(1.0)), [dim], dtype)
    head = helper.create_parameter(
        ParamAttr(name=head_name,
                  initializer=init_mod.Normal(0.0, 0.02)),
        [dim, vocab_size], dtype)

    quant_inputs = {}
    if quantize:
        out_dims = {"Wq": n_heads * hd, "Wk": n_kv_heads * hd,
                    "Wv": n_kv_heads * hd, "Wo": dim}
        if moe_experts == 0:
            out_dims.update({"WGate": ffn_hidden, "WUp": ffn_hidden,
                             "WDown": dim})
        for slot, out_d in out_dims.items():
            w = weights[slot]
            w.dtype = "int8"
            sc = helper.create_parameter(
                ParamAttr(name=w.name + "@scale",
                          initializer=init_mod.Constant(1.0)),
                [n_layers, 1, out_d], "float32")
            quant_inputs[slot + "Scale"] = [sc.name]
        if moe_experts:
            # per-expert x per-output-channel scales; the ROUTER stays
            # float (tiny, and its softmax ranking is what routing IS)
            moe_dims = {"MoeWGate": ffn_hidden, "MoeWUp": ffn_hidden,
                        "MoeWDown": dim}
            for slot, out_d in moe_dims.items():
                wname = moe_inputs[slot][0]
                main = helper.main_program.global_block()
                main.var(wname).dtype = "int8"
                sc = helper.create_parameter(
                    ParamAttr(name=wname + "@scale",
                              initializer=init_mod.Constant(1.0)),
                    [n_layers, moe_experts, 1, out_d], "float32")
                quant_inputs[slot + "Scale"] = [sc.name]
        head.dtype = "int8"
        hsc = helper.create_parameter(
            ParamAttr(name=head.name + "@scale",
                      initializer=init_mod.Constant(1.0)),
            [vocab_size], "float32")
        quant_inputs["LmHeadScale"] = [hsc.name]

    out_shape = [tokens.shape[0], None]
    if tokens.shape[1] is not None and tokens.shape[1] >= 0:
        out_shape[1] = tokens.shape[1] + max_new_tokens
    else:
        out_shape[1] = -1
    out = helper.create_variable_for_type_inference(tokens.dtype,
                                                    shape=out_shape)
    outputs = {"Out": [out.name]}
    probs = None
    if return_probs:
        # first decode step's [batch, vocab] distribution (softmax over
        # the prefill-cache logits) — the probability-level instrument
        # kv_int8 quality is pinned against
        probs = helper.create_variable_for_type_inference(
            "float32", shape=[tokens.shape[0], vocab_size])
        outputs["FirstProbs"] = [probs.name]
    helper.append_op(
        type="llama_generate",
        inputs={"Tokens": [tokens.name], "Emb": [emb.name],
                "FinalNorm": [fnorm.name], "LmHead": [head.name],
                **{slot: [w.name] for slot, w in weights.items()},
                **moe_inputs, **quant_inputs},
        outputs=outputs,
        attrs={"n_heads": n_heads, "n_kv_heads": n_kv_heads,
               "rope_base": rope_base, "epsilon": epsilon,
               "max_new_tokens": max_new_tokens,
               "temperature": temperature, "top_k": top_k,
               "top_p": top_p,
               "eos_id": -1 if eos_id is None else int(eos_id),
               "pad_id": int(pad_id), "moe_top_k": int(moe_top_k),
               "unroll_layers": bool(unroll_layers),
               "decode_unroll": int(decode_unroll),
               "kv_int8": bool(kv_int8),
               "return_probs": bool(return_probs)})
    if return_probs:
        return out, probs
    return out


def _dense_serving_params(helper, *, dtype, vocab_size, dim, n_layers,
                          n_heads, n_kv_heads, ffn_hidden, quantize,
                          emb_name="tok_emb",
                          final_norm_name="final_norm",
                          head_name="lm_head"):
    """The dense generator tensor set (stacked decoder weights + emb /
    final norm / lm head, with int8 ``@scale`` companions when
    ``quantize``) as an op-input slot dict — shared by the paged
    serving ops so they read the exact scope layout
    ``build_llama_generator`` serves from. MoE is a design-out here
    (the paged engine serves dense models; route MoE through
    llama_generate)."""
    hd = dim // n_heads
    weights = _stack_params(helper, dtype, n_layers, n_heads,
                            n_kv_heads, dim, hd, ffn_hidden, None,
                            pp_sharded=False)
    ninit = init_mod.Normal(0.0, 0.02)
    emb = helper.create_parameter(
        ParamAttr(name=emb_name, initializer=ninit),
        [vocab_size, dim], dtype)
    fnorm = helper.create_parameter(
        ParamAttr(name=final_norm_name,
                  initializer=init_mod.Constant(1.0)), [dim], dtype)
    head = helper.create_parameter(
        ParamAttr(name=head_name, initializer=ninit),
        [dim, vocab_size], dtype)
    inputs = {"Emb": [emb.name], "FinalNorm": [fnorm.name],
              "LmHead": [head.name],
              **{slot: [w.name] for slot, w in weights.items()}}
    if quantize:
        out_dims = {"Wq": n_heads * hd, "Wk": n_kv_heads * hd,
                    "Wv": n_kv_heads * hd, "Wo": dim,
                    "WGate": ffn_hidden, "WUp": ffn_hidden,
                    "WDown": dim}
        for slot, out_d in out_dims.items():
            w = weights[slot]
            w.dtype = "int8"
            sc = helper.create_parameter(
                ParamAttr(name=w.name + "@scale",
                          initializer=init_mod.Constant(1.0)),
                [n_layers, 1, out_d], "float32")
            inputs[slot + "Scale"] = [sc.name]
        head.dtype = "int8"
        hsc = helper.create_parameter(
            ParamAttr(name=head.name + "@scale",
                      initializer=init_mod.Constant(1.0)),
            [vocab_size], "float32")
        inputs["LmHeadScale"] = [hsc.name]
    return inputs


def _paged_model_attrs(n_heads, n_kv_heads, rope_base, epsilon,
                       page_size):
    return {"n_heads": n_heads, "n_kv_heads": n_kv_heads,
            "rope_base": rope_base, "epsilon": epsilon,
            "page_size": int(page_size)}


def llama_paged_prefill(tokens, lens, table, k_pages, v_pages, *,
                        vocab_size, dim, n_layers, n_heads, n_kv_heads,
                        ffn_hidden, page_size, rope_base=10000.0,
                        epsilon=1e-6, dtype="float32", quantize=False,
                        name="blocks", emb_name="tok_emb",
                        final_norm_name="final_norm",
                        head_name="lm_head"):
    """Prefill prompts into paged-KV slots (see ops/transformer_ops.py
    llama_paged_prefill). tokens [B, T_bucket] int end-padded; lens [B]
    real lengths; table [B, max_pages] int32; k_pages/v_pages
    [L, n_pages, page_size, n_kv, hd]. Returns (next_tok [B],
    k_pages_out, v_pages_out). Parameter names match
    build_llama_generator's serving layout."""
    helper = LayerHelper("llama_paged_prefill", name=name)
    inputs = _dense_serving_params(
        helper, dtype=dtype, vocab_size=vocab_size, dim=dim,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
        ffn_hidden=ffn_hidden, quantize=quantize, emb_name=emb_name,
        final_norm_name=final_norm_name, head_name=head_name)
    inputs.update({"Tokens": [tokens.name], "Lens": [lens.name],
                   "Table": [table.name], "KPages": [k_pages.name],
                   "VPages": [v_pages.name]})
    nxt = helper.create_variable_for_type_inference(
        tokens.dtype, shape=[tokens.shape[0]])
    kp_out = helper.create_variable_for_type_inference(
        k_pages.dtype, shape=k_pages.shape)
    vp_out = helper.create_variable_for_type_inference(
        v_pages.dtype, shape=v_pages.shape)
    helper.append_op(
        type="llama_paged_prefill", inputs=inputs,
        outputs={"NextTok": [nxt.name], "KPagesOut": [kp_out.name],
                 "VPagesOut": [vp_out.name]},
        attrs=_paged_model_attrs(n_heads, n_kv_heads, rope_base,
                                 epsilon, page_size))
    return nxt, kp_out, vp_out


def llama_paged_prefill_chunk(tokens, lens, offsets, table, k_pages,
                              v_pages, *, vocab_size, dim, n_layers,
                              n_heads, n_kv_heads, ffn_hidden,
                              page_size, rope_base=10000.0,
                              epsilon=1e-6, dtype="float32",
                              quantize=False, name="blocks",
                              emb_name="tok_emb",
                              final_norm_name="final_norm",
                              head_name="lm_head"):
    """Prefill one SLICE of each row's prompt at a per-row offset into
    already-allocated pages (see ops/transformer_ops.py
    llama_paged_prefill_chunk). tokens [B, C] int end-padded to the
    chunk width; lens [B] real tokens in this slice; offsets [B] int32
    absolute start positions; table/k_pages/v_pages as in
    llama_paged_prefill. Returns (next_tok [B] — meaningful on the
    final chunk only, k_pages_out, v_pages_out)."""
    helper = LayerHelper("llama_paged_prefill_chunk", name=name)
    inputs = _dense_serving_params(
        helper, dtype=dtype, vocab_size=vocab_size, dim=dim,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
        ffn_hidden=ffn_hidden, quantize=quantize, emb_name=emb_name,
        final_norm_name=final_norm_name, head_name=head_name)
    inputs.update({"Tokens": [tokens.name], "Lens": [lens.name],
                   "Offsets": [offsets.name], "Table": [table.name],
                   "KPages": [k_pages.name], "VPages": [v_pages.name]})
    nxt = helper.create_variable_for_type_inference(
        tokens.dtype, shape=[tokens.shape[0]])
    kp_out = helper.create_variable_for_type_inference(
        k_pages.dtype, shape=k_pages.shape)
    vp_out = helper.create_variable_for_type_inference(
        v_pages.dtype, shape=v_pages.shape)
    helper.append_op(
        type="llama_paged_prefill_chunk", inputs=inputs,
        outputs={"NextTok": [nxt.name], "KPagesOut": [kp_out.name],
                 "VPagesOut": [vp_out.name]},
        attrs=_paged_model_attrs(n_heads, n_kv_heads, rope_base,
                                 epsilon, page_size))
    return nxt, kp_out, vp_out


def llama_paged_decode(tokens, positions, table, k_pages, v_pages, *,
                       vocab_size, dim, n_layers, n_heads, n_kv_heads,
                       ffn_hidden, page_size, steps=1,
                       rope_base=10000.0, epsilon=1e-6,
                       dtype="float32", quantize=False, name="blocks"):
    """``steps`` greedy decode steps over the paged pools, all slots in
    lockstep (see ops/transformer_ops.py llama_paged_decode). tokens
    [B] last emitted token per slot; positions [B] its absolute
    position. Returns (out_tokens [B, steps], k_pages_out,
    v_pages_out)."""
    helper = LayerHelper("llama_paged_decode", name=name)
    inputs = _dense_serving_params(
        helper, dtype=dtype, vocab_size=vocab_size, dim=dim,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
        ffn_hidden=ffn_hidden, quantize=quantize)
    inputs.update({"Tokens": [tokens.name], "Positions": [positions.name],
                   "Table": [table.name], "KPages": [k_pages.name],
                   "VPages": [v_pages.name]})
    out = helper.create_variable_for_type_inference(
        tokens.dtype, shape=[tokens.shape[0], int(steps)])
    kp_out = helper.create_variable_for_type_inference(
        k_pages.dtype, shape=k_pages.shape)
    vp_out = helper.create_variable_for_type_inference(
        v_pages.dtype, shape=v_pages.shape)
    attrs = _paged_model_attrs(n_heads, n_kv_heads, rope_base,
                               epsilon, page_size)
    attrs["steps"] = int(steps)
    helper.append_op(
        type="llama_paged_decode", inputs=inputs,
        outputs={"OutTokens": [out.name], "KPagesOut": [kp_out.name],
                 "VPagesOut": [vp_out.name]},
        attrs=attrs)
    return out, kp_out, vp_out


def llama_paged_spec_step(tokens, prev, positions, table, k_pages,
                          v_pages, draft_k_pages, draft_v_pages, *,
                          vocab_size, dim, n_layers, n_heads,
                          n_kv_heads, ffn_hidden, draft_dim,
                          draft_n_layers, draft_n_heads,
                          draft_n_kv_heads, draft_ffn_hidden,
                          page_size, gamma=4, rope_base=10000.0,
                          epsilon=1e-6, draft_rope_base=None,
                          draft_epsilon=None, draft_dtype=None,
                          dtype="float32", name="blocks",
                          draft_name="draft"):
    """One speculative round with per-row acceptance (see
    ops/transformer_ops.py llama_paged_spec_step). Returns (emitted
    [B, gamma+1], accepted [B], k_pages_out, v_pages_out,
    draft_k_pages_out, draft_v_pages_out). Draft parameters live under
    ``{draft_name}.*`` exactly as in llama_spec_generate."""
    helper = LayerHelper("llama_paged_spec_step", name=name)
    inputs = _dense_serving_params(
        helper, dtype=dtype, vocab_size=vocab_size, dim=dim,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
        ffn_hidden=ffn_hidden, quantize=False)
    d_helper = LayerHelper("llama_paged_spec_step", name=draft_name)
    d_inputs = _dense_serving_params(
        d_helper, dtype=draft_dtype or dtype, vocab_size=vocab_size,
        dim=draft_dim, n_layers=draft_n_layers, n_heads=draft_n_heads,
        n_kv_heads=draft_n_kv_heads, ffn_hidden=draft_ffn_hidden,
        quantize=False, emb_name=f"{draft_name}.tok_emb",
        final_norm_name=f"{draft_name}.final_norm",
        head_name=f"{draft_name}.lm_head")
    inputs.update({"Draft" + slot: names
                   for slot, names in d_inputs.items()})
    inputs.update({"Tokens": [tokens.name], "Prev": [prev.name],
                   "Positions": [positions.name], "Table": [table.name],
                   "KPages": [k_pages.name], "VPages": [v_pages.name],
                   "DraftKPages": [draft_k_pages.name],
                   "DraftVPages": [draft_v_pages.name]})
    b = tokens.shape[0]
    emitted = helper.create_variable_for_type_inference(
        tokens.dtype, shape=[b, int(gamma) + 1])
    accepted = helper.create_variable_for_type_inference(
        "int32", shape=[b])
    outs = {"Emitted": [emitted.name], "Accepted": [accepted.name]}
    page_outs = []
    for nm, src in (("KPagesOut", k_pages), ("VPagesOut", v_pages),
                    ("DraftKPagesOut", draft_k_pages),
                    ("DraftVPagesOut", draft_v_pages)):
        v = helper.create_variable_for_type_inference(
            src.dtype, shape=src.shape)
        outs[nm] = [v.name]
        page_outs.append(v)
    attrs = _paged_model_attrs(n_heads, n_kv_heads, rope_base,
                               epsilon, page_size)
    attrs.update({"gamma": int(gamma),
                  "draft_n_heads": draft_n_heads,
                  "draft_n_kv_heads": draft_n_kv_heads,
                  "draft_rope_base": (rope_base if draft_rope_base
                                      is None else draft_rope_base),
                  "draft_epsilon": (epsilon if draft_epsilon is None
                                    else draft_epsilon)})
    helper.append_op(type="llama_paged_spec_step", inputs=inputs,
                     outputs=outs, attrs=attrs)
    return (emitted, accepted) + tuple(page_outs)


def llama_spec_generate(tokens, vocab_size, max_new_tokens, *,
                        dim, n_layers, n_heads, n_kv_heads, ffn_hidden,
                        draft_dim, draft_n_layers, draft_n_heads,
                        draft_n_kv_heads, draft_ffn_hidden,
                        gamma=4, rope_base=10000.0, epsilon=1e-6,
                        draft_rope_base=None, draft_epsilon=None,
                        draft_dtype=None, unroll_layers=False,
                        dtype="float32", temperature=0.0,
                        top_k=0, top_p=1.0,
                        eos_id=None, pad_id=0, return_stats=False,
                        name="blocks", draft_name="draft",
                        emb_name="tok_emb",
                        final_norm_name="final_norm",
                        head_name="lm_head"):
    """Speculative decoding (see ops/transformer_ops.py
    llama_spec_generate): a draft model proposes ``gamma`` tokens, the
    target verifies them in one cached forward. At ``temperature`` 0
    the output is EXACTLY the target-only greedy tokens; at
    ``temperature`` > 0 it is speculative SAMPLING (rejection
    resampling), whose every token is distributed exactly as
    llama_generate's sampler with the same
    temperature/``top_k``/``top_p`` (distribution-equal, not
    bitwise-equal — the rng is consumed differently). Target parameter
    names default to the trained ``build_llama`` layout; draft
    parameters live under ``{draft_name}.*`` (plus
    ``{draft_name}.tok_emb`` etc.), so a separately trained small
    model drops in by name.
    """
    _validate_sampling(temperature, top_k, top_p)
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")

    helper = LayerHelper("llama_spec_generate", name=name)
    ninit = init_mod.Normal(0.0, 0.02)
    draft_rope_base = (rope_base if draft_rope_base is None
                       else draft_rope_base)
    draft_epsilon = epsilon if draft_epsilon is None else draft_epsilon
    draft_dtype = dtype if draft_dtype is None else draft_dtype

    def _model_params(h, d, heads, kv, ffn, nl, prefix,
                      model_dtype=dtype):
        hd = d // heads
        weights = _stack_params(h, model_dtype, nl, heads, kv, d, hd,
                                ffn, None, pp_sharded=False)
        emb = h.create_parameter(
            ParamAttr(name=f"{prefix}{emb_name}" if prefix else emb_name,
                      initializer=ninit), [vocab_size, d], model_dtype)
        fnorm = h.create_parameter(
            ParamAttr(name=(f"{prefix}{final_norm_name}" if prefix
                            else final_norm_name),
                      initializer=init_mod.Constant(1.0)), [d],
            model_dtype)
        head = h.create_parameter(
            ParamAttr(name=f"{prefix}{head_name}" if prefix
                      else head_name, initializer=ninit),
            [d, vocab_size], model_dtype)
        return weights, emb, fnorm, head

    t_w, t_emb, t_fn, t_head = _model_params(
        helper, dim, n_heads, n_kv_heads, ffn_hidden, n_layers, "")
    d_helper = LayerHelper("llama_spec_generate", name=draft_name)
    d_w, d_emb, d_fn, d_head = _model_params(
        d_helper, draft_dim, draft_n_heads, draft_n_kv_heads,
        draft_ffn_hidden, draft_n_layers, f"{draft_name}.",
        model_dtype=draft_dtype)

    out_shape = [tokens.shape[0], None]
    if tokens.shape[1] is not None and tokens.shape[1] >= 0:
        out_shape[1] = tokens.shape[1] + max_new_tokens
    else:
        out_shape[1] = -1
    out = helper.create_variable_for_type_inference(tokens.dtype,
                                                    shape=out_shape)
    # acceptance observability: verification rounds taken and tokens
    # emitted — (emitted - 1) / rounds vs the (gamma+1) ceiling is the
    # achieved speculation efficiency (the prefill token is round-free)
    rounds = helper.create_variable_for_type_inference("int32",
                                                       shape=[])
    emitted = helper.create_variable_for_type_inference("int32",
                                                        shape=[])
    helper.append_op(
        type="llama_spec_generate",
        inputs={"Tokens": [tokens.name], "Emb": [t_emb.name],
                "FinalNorm": [t_fn.name], "LmHead": [t_head.name],
                "DraftEmb": [d_emb.name], "DraftFinalNorm": [d_fn.name],
                "DraftLmHead": [d_head.name],
                **{slot: [w.name] for slot, w in t_w.items()},
                **{"Draft" + slot: [w.name] for slot, w in d_w.items()}},
        outputs={"Out": [out.name], "Rounds": [rounds.name],
                 "Emitted": [emitted.name]},
        attrs={"n_heads": n_heads, "n_kv_heads": n_kv_heads,
               "draft_n_heads": draft_n_heads,
               "draft_n_kv_heads": draft_n_kv_heads,
               "rope_base": rope_base, "epsilon": epsilon,
               "draft_rope_base": draft_rope_base,
               "draft_epsilon": draft_epsilon,
               "unroll_layers": bool(unroll_layers),
               "max_new_tokens": int(max_new_tokens),
               "eos_id": -1 if eos_id is None else int(eos_id),
               "pad_id": int(pad_id),
               "temperature": float(temperature),
               "top_k": int(top_k), "top_p": float(top_p),
               "gamma": int(gamma)})
    return (out, rounds, emitted) if return_stats else out


def silu(x, name=None):
    helper = LayerHelper("silu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="silu", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]})
    return out


def block_paged_op(kind, feeds, pools, *, params, lead_params, attrs,
                   vocab_size, dtype, steps=1, name="blocks",
                   lead_name="lead", emb_name="tok_emb",
                   final_norm_name="final_norm", head_name="lm_head",
                   stacks=(), stats=None):
    """One paged step program of a model whose block kinds are attributes
    (ops/transformer_ops.py block_paged_*; models/latent_moe.py builds
    them). ``kind``: ``prefill`` | ``prefill_chunk`` | ``decode``;
    ``feeds``: the op's data inputs by slot (Tokens, Lens, Offsets,
    Positions, Table); ``pools``: the cache pools; ``params`` /
    ``lead_params``: slot -> (suffix, shape, dtype) of the stacked
    layers' and the leading dense layers' parameters, named
    ``{name}.{suffix}`` / ``{lead_name}.{suffix}``. A model that mixes
    attention kinds has no ``params`` but ``stacks``: (slot prefix, scope
    name, table) of each kind's stacked layers, in ``attrs["attn_kinds"]``'
    order, and ``RingTable`` among its feeds; ``stats`` names its Stats
    (PAGED_STATS where None). Returns (tokens, pools_out, logits, picks,
    stats)."""
    from ..ops.transformer_ops import PAGED_STATS
    helper = LayerHelper("block_paged_" + kind, name=name)
    ninit = init_mod.Normal(0.0, 0.02)

    def make(pname, shape, pdtype, init=ninit):
        return helper.create_parameter(
            ParamAttr(name=pname, initializer=init), list(shape), pdtype)

    tables = [("", name, params), ("Lead", lead_name, lead_params)] \
        + list(stacks)
    dim = next((t.get("AttnNorm") or t["AttnPostNorm"])[1][-1]
               for _, _, t in tables if t)
    n_routed = sum(t["MoeRouter"][1][0] for _, _, t in tables
                   if "MoeRouter" in t)
    inputs = {"Emb": [make(emb_name, [vocab_size, dim], dtype).name],
              "FinalNorm": [make(final_norm_name, [dim], dtype,
                                 init_mod.Constant(1.0)).name],
              "LmHead": [make(head_name, [dim, vocab_size], dtype).name]}
    for prefix, scope_name, table in tables:
        for slot, (suffix, shape, pdtype) in table.items():
            inputs[prefix + slot] = [make(f"{scope_name}.{suffix}", shape,
                                          pdtype).name]
    inputs.update({slot: [v.name] for slot, v in feeds.items()})
    inputs["Pools"] = [p.name for p in pools]
    tokens = feeds["Tokens"]
    b = tokens.shape[0]
    shape = [b, int(steps)] if kind == "decode" else [b]
    out = helper.create_variable_for_type_inference(tokens.dtype,
                                                    shape=shape)
    logits = helper.create_variable_for_type_inference(
        "float32", shape=shape + [vocab_size])
    stats = helper.create_variable_for_type_inference(
        "int32", shape=[len(PAGED_STATS if stats is None else stats)])
    picks = helper.create_variable_for_type_inference(
        "int32", shape=shape + [n_routed, int(attrs["moe_top_k"])])
    pools_out = [helper.create_variable_for_type_inference(
        p.dtype, shape=p.shape) for p in pools]
    attrs = dict(attrs)
    if kind == "decode":
        attrs["steps"] = int(steps)
    helper.append_op(
        type="block_paged_" + kind, inputs=inputs,
        outputs={"OutTokens" if kind == "decode" else "NextTok":
                 [out.name], "Logits": [logits.name],
                 "Picks": [picks.name],
                 "PoolsOut": [p.name for p in pools_out],
                 "Stats": [stats.name]},
        attrs=attrs)
    return out, pools_out, logits, picks, stats
