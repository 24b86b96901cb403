"""Pre-flight: abstract memory budget + partitioning check, no device state.

The reference's 405B chapter walks the HBM math by hand (params + grads +
Adam moments vs 80 GB, ``05-training-llama-405b/README.md:191-224``) and
discovers partitioning mistakes at full scale. Here both are automated:
``--preflight`` traces and SPMD-lowers the COMPLETE training step for the
requested (model, mesh, flags) with fully abstract parameters — any
shape/sharding/divisibility error surfaces in seconds on a login host — and
prints the per-device resident-bytes budget derived from the actual
shardings (``NamedSharding.shard_shape``), so "will it fit" is answered
before a single chip is reserved.

It also prints a per-collective ICI comm model + roofline
(``comm_roofline``): ring-collective bytes per chip per step for the plan's
fsdp all-gathers / grad reduce-scatters / megatron tp all-reduces / dp grad
all-reduce / MoE EP exchange / vocab-parallel loss psums, divided by the
target chip's ICI bandwidth, against the step's compute time at peak —
the scaling-book first-order answer to "is the fsdp=32 x tp=8 405B plan
compute-bound on a v5p pod". The collective KINDS in the model are
cross-checked against the compiled HLO at small scale by
``tests/test_405b_recipe.py``.
"""
from __future__ import annotations

import logging

import jax
import numpy as np

LOGGER = logging.getLogger(__name__)


def _per_device_bytes(shapes_tree, shardings_tree) -> int:
    total = 0
    for sd, sh in zip(jax.tree.leaves(shapes_tree), jax.tree.leaves(shardings_tree)):
        shard = sh.shard_shape(sd.shape) if sd.shape else ()
        total += int(np.prod(shard, dtype=np.int64)) * sd.dtype.itemsize
    return total


def _abstract_state_bytes(trainer) -> tuple:
    """``(params, optimizer state)`` bytes one device holds of the trainer's
    state. Abstract shapes only."""
    params_b = _per_device_bytes(trainer.param_shapes, trainer.param_shardings)
    opt_shapes = jax.eval_shape(trainer.optimizer.init, trainer.param_shapes)
    return params_b, _per_device_bytes(opt_shapes,
                                       trainer.opt_shardings_device)


def priced_state_bytes(trainer) -> int:
    """What one device holds of the trainer's state at the update boundary:
    its shard of the params, of the optimizer state and of the transient
    gradients (sharded like the params)."""
    params_b, opt_b = _abstract_state_bytes(trainer)
    return 2 * params_b + opt_b


def device_bytes_limit(device):
    """The device's memory in bytes, or None where it reports none (the CPU;
    a described device, which answers no statistics)."""
    try:
        stats = device.memory_stats() or {}
    except Exception:
        return None
    return int(stats["bytes_limit"]) if stats.get("bytes_limit") else None


def comm_roofline(trainer, *, global_batch: int, seq_length: int,
                  device_kind: str | None = None) -> dict:
    """Analytical per-collective ICI bytes + roofline for the trainer's plan.

    Ring-collective cost model (bytes crossing each chip's ICI links, one
    direction): all-gather / reduce-scatter of a tensor of ``n`` bytes over
    an axis of size ``k`` moves ``(k-1)/k * n``; all-reduce moves
    ``2(k-1)/k * n``. Weight collectives count the fsdp axis only (tp keeps
    its shard resident); activation all-reduces are the 4 megatron
    psums/layer (attn out + mlp out, forward and backward). Counted per
    step at ``global_batch`` x ``seq_length``; bf16 weights/activations,
    fp32 grad reduction.

    ``device_kind`` names the TARGET chip (e.g. "TPU v5p") so a CPU login
    host can evaluate a pod plan; defaults to the local device. Returns the
    table + derived times; does not claim overlap it can't see — both the
    overlapped (max) and serial (sum) MFU ceilings are reported.
    """
    from ..utils.mfu import (banded_attention_kv_length, device_ici_bandwidth,
                             device_peak_flops, transformer_flops_per_token)

    cfg = trainer.bundle.config
    mesh = trainer.plan.mesh.shape
    fsdp = mesh.get("fsdp", 1)
    tp = mesh.get("tp", 1)
    dp = mesh.get("dp", 1)
    ep = mesh.get("ep", 1)
    n_chips = trainer.plan.mesh.devices.size

    e = cfg.hidden_size
    n_layers = cfg.num_layers
    d = cfg.head_size
    hq, hkv = cfg.num_heads * d, getattr(cfg, "num_kv_heads", cfg.num_heads) * d
    inter = getattr(cfg, "intermediate_size", 4 * e)
    # MoE: EVERY expert's weights ride the fsdp all-gather/reduce-scatter
    # (they are resident params), while compute below counts ACTIVE params —
    # conflating the two misprices an MoE pod plan by ~E/k in both directions
    n_experts = getattr(cfg, "num_experts", 1)
    # per-layer weight bytes in the bf16 compute stream, tp-sharded resident
    w_layer = (e * hq + 2 * e * hkv + hq * e
               + n_experts * 3 * e * inter) * 2 / tp
    w_embed = (cfg.vocab_size * e * 2
               * (1 if getattr(cfg, "tie_word_embeddings", False) else 2)) / tp
    weight_bytes = n_layers * w_layer + w_embed

    rows_local = global_batch / max(dp * fsdp, 1)
    act_bytes = rows_local * seq_length * e * 2          # [b_loc, S, E] bf16

    def ag_rs(n, k):
        return (k - 1) / k * n if k > 1 else 0.0

    def ar(n, k):
        return 2 * (k - 1) / k * n if k > 1 else 0.0

    # MoE EP exchange (ragged dispatch, models/moe.py): per MoE layer the
    # token rows [t_loc, D] bf16 cross ep once out (all-gather) and once
    # back (reduce-scatter); forward AND backward transpose
    ep_exchange = (4 * n_layers * ag_rs(act_bytes, ep)
                   if n_experts > 1 else 0.0)
    # vocab-parallel loss psums ([b_loc, S] fp32 rows: max-gather, sumexp,
    # picked — fwd + the bwd dh reduce), counted when the plan shards vocab
    # on tp
    loss_bytes = rows_local * seq_length * 4
    loss_psum = 4 * ar(loss_bytes, tp) if tp > 1 else 0.0
    table = {
        # fwd all-gather + bwd re-gather of every weight over fsdp
        "fsdp_allgather_weights": 2 * ag_rs(weight_bytes, fsdp),
        # grad reduce-scatter over fsdp, fp32 accumulation stream
        "fsdp_reducescatter_grads": ag_rs(weight_bytes * 2, fsdp),
        # 4 megatron all-reduces per layer on [b_loc, S, E]
        "tp_allreduce_activations": 4 * n_layers * ar(act_bytes, tp),
        # pure-dp grad all-reduce of the (fsdp x tp)-sharded grads
        "dp_allreduce_grads": ar(weight_bytes * 2 / max(fsdp, 1), dp),
        # MoE expert-parallel token exchange (0 for dense models / ep=1)
        "ep_exchange": ep_exchange,
        # vocab-parallel loss psums (0 unless vocab shards on tp)
        "loss_psum": loss_psum,
    }
    comm_bytes = sum(table.values())

    ici = device_ici_bandwidth(device_kind=device_kind)
    peak = device_peak_flops(device_kind=device_kind)
    # active params (MoE: k of E experts), matching the trainer's own MFU
    # accounting (cli.py) — total params would overstate compute ~E/k x.
    # Attention is priced BANDED — O(S*window) per the config's window
    # schedule, not dense O(S^2) — because the roofline's job is the honest
    # time estimate for THIS program (the banded kernel skips out-of-band
    # kv tiles); the cli's MFU keeps the conventional dense count so numbers
    # stay comparable with published figures (compare step_ms across
    # windowed A/Bs, not the MFU column)
    attn_kv = banded_attention_kv_length(cfg, seq_length)
    flops_per_token = transformer_flops_per_token(
        trainer.bundle.num_active_params(), n_layers, e, seq_length,
        vocab_size=cfg.vocab_size, attn_kv_len=attn_kv)
    t_comp = (flops_per_token * global_batch * seq_length) / (peak * n_chips)
    t_comm = comm_bytes / ici

    return {
        "attn_kv_len": attn_kv,   # mean keys/query: < seq_length iff banded
        "per_collective_bytes_per_chip": {k: int(v) for k, v in table.items()},
        "comm_bytes_per_chip": int(comm_bytes),
        "ici_bytes_per_s": ici,
        "peak_flops_per_chip": peak,
        "t_compute_s": t_comp,
        "t_comm_s": t_comm,
        "comm_to_compute": t_comm / t_comp if t_comp else float("inf"),
        # ceilings on ACHIEVABLE MFU from comm alone (kernel efficiency
        # excluded): overlapped = comm hides behind compute; serial = none
        "mfu_ceiling_overlapped": t_comp / max(t_comp, t_comm) if t_comp else 0.0,
        "mfu_ceiling_serial": t_comp / (t_comp + t_comm) if t_comp else 0.0,
    }


def _tree_bytes(shapes_tree) -> int:
    return sum(int(np.prod(sd.shape, dtype=np.int64)) * sd.dtype.itemsize
               for sd in jax.tree.leaves(shapes_tree))


def price_post_colocation(trainer, *, n_slots: int, page_size: int = 16,
                          max_len: int = 2048, kv_dtype=None,
                          weight_dtype=None, teacher_bundle=None,
                          budget_bytes: int | None = None) -> dict:
    """Price the post-training loop's CO-RESIDENT memory — everything
    that must live on the chip at once for rollout→score→update→publish
    (post/loop.py): the trainer's policy state (params + optimizer
    moments — adapter-only under ``lora_only`` — + transient grads), the
    serve engine's MERGED policy copy and its page pool, and an optional
    teacher/reward model's params. Abstract shapes only, no device
    state; with ``budget_bytes`` an impossible colocation REFUSES here,
    before any compile burns minutes discovering it as an OOM."""
    from ..serve.kv_pages import kv_dtype_name, kv_page_bytes, \
        pages_for_tokens

    cfg = trainer.bundle.config
    params_b, opt_b = _abstract_state_bytes(trainer)
    grad_b = params_b          # transient, resident at the update boundary
    # the engine serves the MERGED policy (base layout for LoRA bundles),
    # priced at the engine's weight_dtype: the QLoRA colocation is a
    # quantized base copy + fp adapters in the trainer + the teacher,
    # and it is exactly the int8 engine copy that makes all three fit
    base_bundle = getattr(trainer.bundle, "lora_base", trainer.bundle)
    engine_shapes = jax.eval_shape(
        lambda: base_bundle.init(cfg, jax.random.key(0)))
    if weight_dtype is None:
        wname = "model"
        engine_params_b = _tree_bytes(engine_shapes)
    else:
        from ..serve.weights import weight_dtype_name, weight_tree_bytes
        wname = weight_dtype_name(cfg, weight_dtype)
        engine_params_b = weight_tree_bytes(
            engine_shapes, wname, getattr(base_bundle, "family", None))
    n_pages = 1 + n_slots * pages_for_tokens(max_len, page_size)
    pool_b = kv_page_bytes(cfg, page_size=page_size, n_pages=n_pages,
                           kv_dtype=kv_dtype_name(cfg, kv_dtype))
    teacher_b = 0
    if teacher_bundle is not None:
        teacher_b = _tree_bytes(jax.eval_shape(
            lambda: teacher_bundle.init(teacher_bundle.config,
                                        jax.random.key(0))))
    total = params_b + opt_b + grad_b + engine_params_b + pool_b + teacher_b
    report = {
        "policy_param_bytes": params_b,
        "policy_opt_state_bytes": opt_b,
        "policy_grad_bytes_transient": grad_b,
        "engine_param_bytes": engine_params_b,
        "engine_weight_dtype": wname,
        "engine_pool_bytes": pool_b,
        "engine_pool_pages": n_pages,
        "teacher_param_bytes": teacher_b,
        "total_bytes": total,
        "lora_only": bool(getattr(trainer, "lora_only", False)),
    }
    gib = 1 / 2**30
    LOGGER.info(
        f"post colocation: policy {params_b * gib:.3f} GiB params + "
        f"{opt_b * gib:.3f} GiB opt + {grad_b * gib:.3f} GiB grads, "
        f"engine {engine_params_b * gib:.3f} GiB merged copy + "
        f"{pool_b * gib:.3f} GiB pool ({n_pages} pages), teacher "
        f"{teacher_b * gib:.3f} GiB -> total {total * gib:.3f} GiB"
        + (f" vs budget {budget_bytes * gib:.3f} GiB"
           if budget_bytes else ""))
    if budget_bytes is not None and total > budget_bytes:
        raise ValueError(
            f"post-training colocation needs {total} bytes "
            f"({total * gib:.2f} GiB: policy state "
            f"{(params_b + opt_b + grad_b) * gib:.2f} + engine "
            f"{(engine_params_b + pool_b) * gib:.2f} + teacher "
            f"{teacher_b * gib:.2f}) but the budget is {budget_bytes} "
            f"({budget_bytes * gib:.2f} GiB) — shrink the pool "
            f"(n_slots/max_len/kv_dtype), use LoRA adapters "
            f"(lora_only), or drop the co-resident teacher")
    return report


def run_preflight(trainer, *, global_batch: int, seq_length: int,
                  target_device: str | None = None) -> dict:
    """Lower the train step abstractly and report the per-device budget.

    Returns the report dict (also logged) — keys in bytes unless noted.
    ``target_device`` names the pod's chip for the comm roofline (e.g.
    "v5p") when preflighting from a non-TPU login host; defaults to the
    local device on TPU, v5p otherwise.
    """
    from .step import lower_step

    lowered, state = lower_step(trainer, global_batch=global_batch,
                                seq_length=seq_length)

    params_b = _per_device_bytes(state.params, trainer.param_shardings)
    opt_b = _per_device_bytes(
        state.opt_state,
        jax.tree.map(lambda s: s.sharding, state.opt_state))
    # grads are transient but resident at the optimizer boundary, sharded
    # like the params; their dtype is the policy's accum-buffer dtype when
    # accumulating, else the param storage dtype (what value_and_grad yields)
    def grad_bytes(param_shapes, dtype):
        return _per_device_bytes(
            jax.tree.map(
                lambda sd: jax.ShapeDtypeStruct(
                    sd.shape, dtype if dtype is not None else sd.dtype),
                param_shapes),
            trainer.param_shardings)

    policy = trainer.precision
    grad_b = grad_bytes(state.params,
                        policy.accum_dtype if trainer.grad_accum > 1 else None)
    report = {
        "per_device_param_bytes": params_b,
        "per_device_opt_state_bytes": opt_b,
        "per_device_grad_bytes_transient": grad_b,
        "per_device_state_total_bytes": params_b + opt_b,
        "n_devices": trainer.plan.mesh.devices.size,
        "mesh": dict(trainer.plan.mesh.shape),
        "lowered": True,
    }
    # price the precision policy against the fp32 baseline (the 16 B/param
    # math of 05/README.md): same plan, unwrapped optimizer, fp32 leaves —
    # so "how much HBM did the policy buy" is a reported number, not a claim
    fp32_sh = trainer.fp32_state_shardings
    fp32_opt_shapes = jax.eval_shape(trainer.base_optimizer.init,
                                     trainer.fp32_param_shapes)
    params32_b = _per_device_bytes(trainer.fp32_param_shapes,
                                   trainer.param_shardings)
    opt32_b = _per_device_bytes(fp32_opt_shapes, fp32_sh.opt_state)
    grad32_b = grad_bytes(trainer.fp32_param_shapes, np.float32)
    total_b, total32_b = params_b + opt_b + grad_b, params32_b + opt32_b + grad32_b
    report["precision"] = {
        "policy": policy.name,
        "per_device_opt_state_bytes_fp32": opt32_b,
        "per_device_total_bytes_fp32": total32_b,
        "opt_state_reduction": round(opt32_b / opt_b, 2) if opt_b else 1.0,
        "total_state_reduction": (round(total32_b / total_b, 2)
                                  if total_b else 1.0),
    }
    limit = device_bytes_limit(jax.devices()[0])
    if limit:
        report["device_bytes_limit"] = limit
    gib = 1 / 2**30
    LOGGER.info(
        f"preflight OK: step lowers on mesh {report['mesh']}; per device "
        f"params {params_b * gib:.2f} GiB + opt {opt_b * gib:.2f} GiB "
        f"(+ transient grads {grad_b * gib:.2f} GiB)"
        + (f"; device limit {report['device_bytes_limit'] * gib:.2f} GiB"
           if "device_bytes_limit" in report else ""))
    LOGGER.info(
        f"precision policy '{policy.name}': optimizer state "
        f"{report['precision']['opt_state_reduction']:.2f}x smaller than "
        f"fp32, total state (params+opt+grads) "
        f"{report['precision']['total_state_reduction']:.2f}x smaller "
        f"({total32_b * gib:.2f} -> {total_b * gib:.2f} GiB per device)")

    cfg = trainer.bundle.config
    if hasattr(cfg, "num_experts"):
        # price the MoE dispatch transients per layer at this (batch, seq):
        # dense = the [E, C, D] input + [E, C, F] inner + [E, C, D] output
        # capacity buffers (padding included); ragged = the same three over
        # the [kT, *] sorted buffer — the dense/ragged ratio IS the padding
        # waste (E*C / kT), what moe_dispatch="ragged" deletes
        import math as _math

        t = global_batch * seq_length
        k, e_cnt = cfg.experts_per_token, cfg.num_experts
        cap = max(int(_math.ceil(cfg.capacity_factor * k * t / e_cnt)), 1)
        itemsize = jax.numpy.dtype(cfg.dtype).itemsize
        d_model, f_ff = cfg.hidden_size, cfg.intermediate_size
        dense_b = e_cnt * cap * (2 * d_model + f_ff) * itemsize
        ragged_b = k * t * (2 * d_model + f_ff) * itemsize
        mode = getattr(cfg, "moe_dispatch", "dense")
        report["moe_dispatch"] = {
            "mode": mode,
            "per_layer_dense_dispatch_bytes": dense_b,
            "per_layer_ragged_dispatch_bytes": ragged_b,
            "dense_over_ragged": round(dense_b / ragged_b, 2),
        }
        LOGGER.info(
            f"moe dispatch '{mode}': per-layer transients dense "
            f"{dense_b / 2**20:.0f} MiB ([E={e_cnt}, C={cap}] capacity "
            f"buffers) vs ragged {ragged_b / 2**20:.0f} MiB ([kT={k * t}] "
            f"sorted buffer) — {dense_b / ragged_b:.2f}x padding")

    # serving-side KV pricing (serve/kv_pages.py): what ONE decode slot of
    # this model costs at the training context, in pages — pages x layers x
    # page_size x what the family caches for a token (kv_pages.pool_layout:
    # k and v of kv_heads x head_dim, or a latent row). Training answers
    # "does the step fit"; this row answers the follow-on "how many
    # concurrent requests fit next to the weights when the checkpoint
    # serves" before anyone sizes a pool by trial and error.
    from ..serve.kv_pages import KV_DTYPES, is_latent, kv_page_bytes, \
        num_kv_heads, pages_for_tokens

    page_size = 16
    pages_per_slot = pages_for_tokens(seq_length, page_size)
    per_page = kv_page_bytes(cfg, page_size=page_size)
    per_slot = per_page * pages_per_slot
    # sharded pool (serve/sharding.py): under tp the pool splits on the
    # kv-head axis, so each chip holds per_page / tp — the number that
    # actually bounds co-resident requests on a tp-serving mesh. Priced
    # off THIS plan's tp under EXACTLY validate_kv_shard's contract
    # (tp-only mesh, tp divides both head counts) — a per-chip figure
    # the engine would refuse to build must never reach the report.
    tp = int(trainer.plan.mesh.shape["tp"])
    kv_shards = tp if (
        tp > 1 and all(a == "tp" for a in trainer.plan.active_axes())
        and not is_latent(cfg)      # one latent row: no kv head to split
        and num_kv_heads(cfg) % tp == 0 and cfg.num_heads % tp == 0) else 1
    # per-generated-token decode traffic: the flash-decode kernel
    # (ops/paged_decode.py) READS the live context's pages through the
    # block table and writes only the [S, Hq, D] output — O(context)
    # bytes. The old gather path materialized the full [M*page] logical
    # view per step: read the pool, WRITE the view, read it back in the
    # attend — ~3x the kernel's traffic, plus a context-sized transient.
    kernel_read = per_slot
    gather_traffic = 3 * per_slot
    # prefix sharing: a P-token shared system prompt is resident ONCE; at
    # n slots it amortizes (n-1) x its pages (512 tokens as the nominal
    # system-prompt size, clamped to the context)
    shared_tokens = min(512, seq_length)
    shared_bytes = per_page * (shared_tokens // page_size)
    report["serve_kv"] = {
        "page_size": page_size,
        "pages_per_slot_at_seq": pages_per_slot,
        "bytes_per_page": per_page,
        "bytes_per_slot_at_seq": per_slot,
        # dense-cache equivalent: a contiguous [slots, max_position] cache
        # pays the POSITION TABLE per slot whatever the live context is —
        # the ratio is what the paged pool saves at this seq_length
        "dense_bytes_per_slot": kv_page_bytes(
            cfg, page_size=1, n_pages=cfg.max_position_embeddings),
        "decode_read_bytes_per_token_flash": kernel_read,
        "decode_traffic_bytes_per_token_gather": gather_traffic,
        "shared_prefix_tokens_nominal": shared_tokens,
        "shared_prefix_bytes_amortized_per_extra_slot": shared_bytes,
        # sharded-pool column: the per-CHIP page/slot bytes next to the
        # replicated cost above (equal when kv_shards == 1)
        "kv_shards": kv_shards,
        "bytes_per_page_per_chip": per_page // kv_shards,
        "bytes_per_slot_per_chip_at_seq": per_slot // kv_shards,
        # disaggregated handoff (serve/disagg.py): same-host transfer is
        # a refcount move — 0 bytes; a cross-host transfer would move the
        # sequence's committed k/v payload (the per-slot bytes above)
        "handoff_bytes_same_host": 0,
        "handoff_bytes_cross_host_at_seq": per_slot,
    }
    # multi-token paged forwards (the block_q=T kernel family,
    # ops/paged_decode.py): a speculative VERIFY step ([S, k+1] per slot)
    # and a chunked-prefill chunk ([1, C]) read the slot's live context
    # ONCE through the block table — the same O(context) kernel bytes the
    # decode row above pays, amortized over the T tokens the forward
    # emits/commits — while the gather form pays the ~3x logical-view
    # round-trip PER FORWARD. Decode was already priced per token; these
    # are the multi-token rows that used to be gather-only.
    report["serve_kv"].update({
        "verify_read_bytes_per_step_flash": kernel_read,
        "verify_traffic_bytes_per_step_gather": gather_traffic,
        "chunk_prefill_read_bytes_per_chunk_flash": kernel_read,
        "chunk_prefill_traffic_bytes_per_chunk_gather": gather_traffic,
    })
    # kv_dtype column (serve/kv_pages.py): every per-page/per-slot figure
    # above parameterizes on the pool's storage dtype — int8 rows INCLUDE
    # the per-(position, kv-head) fp32 scales (payload bytes alone would
    # overstate the win). The same ratio applies to the decode read, the
    # cross-host handoff payload, and the slots-per-HBM-byte capacity.
    by_dtype = {name: kv_page_bytes(cfg, page_size=page_size, kv_dtype=name)
                for name in KV_DTYPES}
    slot_by_dtype = {name: b * pages_per_slot for name, b in by_dtype.items()}
    int8_ratio = round(by_dtype["int8"] / by_dtype["fp32"], 4)
    report["serve_kv"].update({
        "bytes_per_page_by_kv_dtype": by_dtype,
        "bytes_per_slot_by_kv_dtype": slot_by_dtype,
        "int8_bytes_vs_fp32": int8_ratio,
        # cross-host handoff wire (serve/transport.py): one transfer
        # moves the sequence's pool leaves as raw bytes, so the payload
        # IS the per-slot bytes at the pool's kv_dtype (int8 ships its
        # fp32 scales and still ~thirds the frame; the ~few-hundred-byte
        # header/CRC envelope vanishes against any real context) — the
        # wire keys alias the slot table rather than re-deriving it
        "handoff_wire_bytes_by_kv_dtype": slot_by_dtype,
        "handoff_wire_int8_vs_fp32": int8_ratio,
    })
    # tiered KV (serve/tiering.py): the host tier holds spilled pool
    # payloads at the pool's storage dtype, so one preempted sequence (or
    # one prefix chain of the same length) parks bytes_per_slot of host
    # RAM per spilled slot — the row that sizes ``host_tier_bytes``
    # (budget // bytes_per_spilled_slot = resumable sequences). A fleet
    # directory pull moves those same bytes ONCE over the wire instead of
    # re-prefilling: re-prefill at the training context costs
    # ~2 * active_params * seq_length FLOPs, so the ratio row is the
    # FLOPs a hit saves per wire byte it spends.
    active_params = trainer.bundle.num_active_params()
    reprefill_flops = 2 * active_params * seq_length
    report["serve_kv"].update({
        "host_tier_bytes_per_spilled_slot_at_seq": per_slot,
        "host_tier_bytes_per_spilled_slot_by_kv_dtype": slot_by_dtype,
        "host_tier_slots_per_gib": max(1, (1 << 30) // per_slot),
        "directory_pull_wire_bytes_at_seq": per_slot,
        "reprefill_flops_at_seq": reprefill_flops,
        "reprefill_flops_per_pull_byte": round(
            reprefill_flops / per_slot, 2),
    })
    # speculative decoding (serve/spec.py): decode's OTHER traffic is the
    # weight read — every spec-off token pays the full per-chip param
    # bytes. A verify step amortizes one weight pass over the accepted
    # run; with per-position acceptance rate a and depth k the expected
    # emitted tokens per pass are 1 + a + a^2 + ... + a^k (the accepted
    # prefix is geometric), so the per-token weight bytes divide by that.
    spec_k = 4
    def _amortized(a: float) -> int:
        tokens = sum(a ** j for j in range(spec_k + 1))
        return int(params_b / tokens)
    report["serve_kv"].update({
        "spec_k_nominal": spec_k,
        "weight_read_bytes_per_token_spec_off": params_b,
        "weight_read_bytes_per_token_spec_accept_0.7": _amortized(0.7),
        "weight_read_bytes_per_token_spec_accept_1.0": _amortized(1.0),
        # the kv-side twin of the weight amortization: one flash verify
        # forward's O(context) read divided over its k+1 emitted tokens
        # at full acceptance (the gather form paid 3x this, per forward)
        "verify_read_bytes_per_token_flash_accept_1.0":
            kernel_read // (spec_k + 1),
    })
    LOGGER.info(
        f"serve KV pricing: {per_page / 2**10:.1f} KiB/page "
        f"({page_size} tokens) -> {per_slot / 2**20:.2f} MiB per decode "
        f"slot at context {seq_length} ({pages_per_slot} pages; a dense "
        f"max_position cache would hold "
        f"{report['serve_kv']['dense_bytes_per_slot'] / 2**20:.2f} MiB "
        f"per slot"
        + (f"; kv-head-sharded pool: {per_slot / kv_shards / 2**20:.2f} "
           f"MiB per chip at tp={kv_shards}" if kv_shards > 1 else "")
        + f"); int8 KV pages (kv_dtype='int8', scales included) cut a page "
        f"to {by_dtype['int8'] / 2**10:.1f} KiB — "
        f"{by_dtype['int8'] / by_dtype['fp32']:.2f}x of fp32, the same "
        f"factor on decode reads and the cross-host handoff payload"
        f"; decode reads {kernel_read / 2**20:.2f} MiB/token "
        f"through the paged flash kernel (the gather view moved "
        f"~{gather_traffic / 2**20:.2f} MiB/token; verify and prefill "
        f"chunks pay the same O(context) kernel read ONCE per multi-token "
        f"forward — the block_q=T rows above); a {shared_tokens}-token "
        f"shared prefix amortizes {shared_bytes / 2**20:.2f} MiB per "
        f"additional co-resident slot; prefill->decode handoff moves 0 B "
        f"same-host (refcount transfer), {per_slot / 2**20:.2f} MiB "
        f"cross-host at this context; speculative decode at k={spec_k} "
        f"amortizes the {params_b / 2**20:.0f} MiB/chip weight read to "
        f"{_amortized(0.7) / 2**20:.0f} MiB/token at 0.7 acceptance "
        f"({_amortized(1.0) / 2**20:.0f} at full)")

    # decode horizons (serve/engine.py horizon_for): the spec rows
    # amortize the WEIGHT read per token; decode_horizon=K amortizes the
    # HOST round-trip — one dispatch + one [n_slots, K] int32 readback
    # per K steps instead of per step. The device-side KV/weight traffic
    # above is UNCHANGED (the horizon is the same per-step program under
    # a scan); what K buys is dispatches/step = 1/K, and what it costs
    # is worst-case page pre-reservation per active slot per horizon
    # (reserve_horizon grants a SHORTER horizon on pressure — never a
    # mid-horizon host allocation) plus a K-burst emission shape the
    # loadgen's itl_p99 prices.
    horizon_k = 8
    report["serve_kv"].update({
        "decode_horizon_nominal": horizon_k,
        "horizon_dispatches_per_step": round(1 / horizon_k, 4),
        "horizon_block_bytes_per_slot": horizon_k * 4,
        # pages a K-horizon may need per slot beyond its committed
        # length, at the worst page phase (len % page == page - 1)
        "horizon_reserve_pages_worst_case":
            -(-(page_size - 1 + horizon_k) // page_size),
    })

    # weight_dtype column (serve/weights.py): the params are the decode
    # step's OTHER byte stream, and with int8 KV they are the largest
    # remaining HBM tenant. Rows are STORAGE bytes per dtype — int8
    # includes the per-block fp32 scales (payload alone would overstate
    # the win, same rule as the kv rows above). The publish/swap payload
    # IS the storage: a quantized-layout publish or an engine-generation
    # swap moves exactly these bytes, and an fp-layout publish into a
    # quantized engine moves the fp32 row once before the engine
    # re-quantizes on-device. The int8 row appears only for families
    # with a leaf-selection rule (llama); others refuse before compile.
    from ..serve.weights import weight_bytes_by_dtype
    serve_bundle = getattr(trainer.bundle, "lora_base", trainer.bundle)
    weight_shapes = jax.eval_shape(
        lambda: serve_bundle.init(cfg, jax.random.key(0)))
    w_by_dtype = weight_bytes_by_dtype(
        weight_shapes, getattr(serve_bundle, "family", None))
    report["serve_weights"] = {
        "weight_bytes_by_dtype": w_by_dtype,
        "publish_payload_bytes_by_dtype": dict(w_by_dtype),
        "swap_payload_bytes_by_dtype": dict(w_by_dtype),
        "int8_supported": "int8" in w_by_dtype,
    }
    if "int8" in w_by_dtype:
        w_ratio = round(w_by_dtype["int8"] / w_by_dtype["fp32"], 4)
        report["serve_weights"]["int8_bytes_vs_fp32"] = w_ratio
        LOGGER.info(
            f"serve weight pricing: params {w_by_dtype['fp32'] / 2**20:.2f}"
            f" MiB fp32 / {w_by_dtype['bf16'] / 2**20:.2f} MiB bf16 / "
            f"{w_by_dtype['int8'] / 2**20:.2f} MiB int8 (block scales "
            f"included, {w_ratio:.2f}x of fp32) — the same factor on every "
            f"publish/swap payload and on the per-token weight read above")
    else:
        LOGGER.info(
            f"serve weight pricing: params {w_by_dtype['fp32'] / 2**20:.2f}"
            f" MiB fp32 / {w_by_dtype['bf16'] / 2**20:.2f} MiB bf16; no "
            f"int8 leaf-selection rule for this family (serve/weights.py)")

    # adapter-pool column (serve/adapters.py): the multi-LoRA pool is a
    # fixed device-resident stack sized at CONSTRUCTION — (max_adapters,
    # rank, targets) prices it exactly, and a tenant insert/republish
    # moves one adapter's factors, never the pool. Rows use the default
    # serving pool shape so the numbers pin arithmetically; scale
    # linearly in max_adapters and rank for other shapes. Priced for
    # families with the grouped-GEMM lora decode path (llama); others
    # would refuse at engine construction.
    from ..models.registry import family_module
    try:
        fam_mod = family_module(getattr(serve_bundle, "family", ""))
    except KeyError:
        fam_mod = None
    if hasattr(fam_mod, "_lora_sort"):
        from ..serve.adapters import (DEFAULT_TARGETS, adapter_nbytes,
                                      adapter_pool_bytes)
        pool_slots, pool_rank = 8, 8
        per_adapter = adapter_nbytes(cfg, rank=pool_rank,
                                     targets=DEFAULT_TARGETS,
                                     bundle=serve_bundle)
        pool_total = adapter_pool_bytes(cfg, max_adapters=pool_slots,
                                        rank=pool_rank,
                                        targets=DEFAULT_TARGETS,
                                        bundle=serve_bundle)
        report["serve_adapters"] = {
            "max_adapters": pool_slots,
            "rank": pool_rank,
            "targets": list(DEFAULT_TARGETS),
            "bytes_per_adapter": per_adapter,
            "pool_bytes": pool_total,
            "publish_payload_bytes": per_adapter,
            "pool_vs_fp32_weights": round(pool_total
                                          / w_by_dtype["fp32"], 4),
        }
        LOGGER.info(
            f"serve adapter pricing: pool {pool_total / 2**20:.2f} MiB "
            f"at (max_adapters={pool_slots}, rank={pool_rank}, "
            f"targets={','.join(DEFAULT_TARGETS)}) — "
            f"{pool_total / w_by_dtype['fp32']:.3f}x of the fp32 params "
            f"for {pool_slots - 1} co-resident tenants; a tenant "
            f"insert/republish moves {per_adapter / 2**10:.1f} KiB "
            f"(vs {w_by_dtype['fp32'] / 2**20:.2f} MiB for a full "
            f"publish_params), retrace-free either way")

    if target_device is None and jax.default_backend() != "tpu":
        target_device = "v5p"  # the 405B recipe's stated target pod
    comm = comm_roofline(trainer, global_batch=global_batch,
                         seq_length=seq_length, device_kind=target_device)
    report["comm"] = comm
    mib = 1 / 2**20
    rows = "; ".join(f"{k} {v * mib:.0f} MiB" for k, v in
                     comm["per_collective_bytes_per_chip"].items() if v)
    banded = (f"; attention priced banded (mean {comm['attn_kv_len']:.0f} "
              f"keys/query vs dense {seq_length})"
              if comm["attn_kv_len"] < seq_length else "")
    LOGGER.info(
        f"comm roofline ({target_device or 'local device'}): "
        f"{rows or 'no cross-chip collectives'} | "
        f"t_comm {comm['t_comm_s'] * 1e3:.1f} ms vs t_compute "
        f"{comm['t_compute_s'] * 1e3:.1f} ms -> MFU ceiling "
        f"{comm['mfu_ceiling_overlapped']:.1%} overlapped / "
        f"{comm['mfu_ceiling_serial']:.1%} serial{banded}")
    del lowered
    return report
