"""Serve a model from the zoo: offline batch generation or an HTTP
endpoint, both through the continuous-batching paged-KV engine.

    # offline: three hermetic requests co-batched on 4 slots
    python -m distributed_training_guide_tpu.serve -m llama-debug \\
        --prompt-ids 3,17,42 --prompt-ids 5,6 --prompt-ids 9 \\
        --steps 16 --n-slots 4

    # online: HTTP endpoint (POST /generate, GET /healthz)
    python -m distributed_training_guide_tpu.serve -m gpt2 \\
        --pretrained /ckpts/gpt2-conv --http-port 8000
"""
from __future__ import annotations

import argparse
import json
import threading
import time


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        prog="python -m distributed_training_guide_tpu.serve")
    parser.add_argument("-m", "--model-name", required=True)
    parser.add_argument("--prompt-ids", action="append", default=[],
                        metavar="IDS", help="comma-separated token ids; "
                        "repeat for several requests (hermetic path)")
    parser.add_argument("--prompt", action="append", default=[],
                        help="text prompt (needs the model's tokenizer in "
                        "the local cache); repeatable")
    parser.add_argument("--steps", type=int, default=32,
                        help="max new tokens per request")
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--top-p", type=float, default=1.0)
    parser.add_argument("--eos-id", type=int, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--n-slots", type=int, default=4,
                        help="concurrent decode slots (the compiled batch)")
    parser.add_argument("--page-size", type=int, default=16,
                        help="tokens per KV page")
    parser.add_argument("--n-pages", type=int, default=None,
                        help="KV pool size in pages (default: full "
                        "residency; smaller engages admission backpressure)")
    parser.add_argument("--max-len", type=int, default=None,
                        help="max prompt+generation context per request "
                        "(default: the model's position table)")
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="stream prompts in N-token chunks co-scheduled "
                        "with resident decodes (Sarathi chunked prefill; "
                        "default: the engine's own size)")
    parser.add_argument("--no-prefix-cache", action="store_true",
                        help="disable copy-on-write prefix sharing of "
                        "prompt pages across requests")
    parser.add_argument("--attend-impl", default="auto",
                        choices=("auto", "flash", "xla"),
                        help="paged attend family for every forward "
                        "(decode, spec verify, prefill chunk): the Pallas "
                        "block_q=T block-table kernel "
                        "('flash', TPU), the gather reference ('xla'), or "
                        "platform auto-dispatch")
    parser.add_argument("--kv-dtype", default=None,
                        choices=("fp32", "bf16", "int8"),
                        help="KV page pool storage (default: the model "
                        "dtype). 'int8' stores block-wise absmax-quantized "
                        "payloads with per-(position, kv-head) fp32 scales "
                        "— ~3x more pages per pool byte, dequantized "
                        "in-kernel on the decode read; the kv_report line "
                        "prices it. The compiled kernel takes int8 pools at "
                        "the same page sizes as float ones")
    parser.add_argument("--weight-dtype", default=None,
                        choices=("fp32", "bf16", "int8"),
                        help="param storage (default: the model dtype). "
                        "'int8' stores block-wise absmax-quantized "
                        "projection weights with per-(row, 32-col-block) "
                        "fp32 scales, dequantized inside the matmul loop "
                        "— ~3.5x smaller params AND the same factor off "
                        "every publish/swap payload (llama family only; "
                        "the weight_report line prices it). Baked per "
                        "fleet like --kv-dtype: all replicas share it")
    parser.add_argument("--speculate", default="off",
                        choices=("off", "ngram", "draft"),
                        help="speculative decoding: 'ngram' is the "
                        "model-free prompt-lookup drafter, 'draft' runs "
                        "a co-resident --draft-model; verification is "
                        "exact — spec-on output is token-identical to "
                        "spec-off at any temperature")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="speculation depth: candidate tokens drafted "
                        "per slot per iteration")
    parser.add_argument("--draft-model", default=None, metavar="NAME",
                        help="model zoo name for --speculate draft (a "
                        "debug-size family; loads --draft-pretrained or "
                        "random-inits, which only demos the machinery)")
    parser.add_argument("--draft-pretrained", default=None, metavar="DIR",
                        help="converted checkpoint dir for the draft model")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated serving: separate prefill and "
                        "decode engines connected by a KV-page handoff "
                        "(DistServe) instead of the monolithic engine")
    parser.add_argument("--prefill-slots", type=int, default=1,
                        help="concurrent prefill slots of the --disagg "
                        "prefill engine")
    parser.add_argument("--transport", default="same_host",
                        choices=("same_host", "cross_host"),
                        help="--disagg handoff transport: 'same_host' "
                        "moves refcounts over one pool (0 bytes); "
                        "'cross_host' runs the multi-host branch — two "
                        "pools, the sequence's serialized k/v payload "
                        "over the crash-safe serve/transport.py wire")
    parser.add_argument("--replicas", type=int, default=1,
                        help="front N engine replicas with the fleet "
                        "router (serve/router.py): prefix-affinity + "
                        "least-loaded routing, heartbeat fencing, "
                        "resubmission replay; replicas share one "
                        "compiled-program cache")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel mesh size for serving "
                        "(params shard as in training)")
    parser.add_argument("--shard-kv", action="store_true",
                        help="shard the KV page pool on the kv-head axis "
                        "over the --tp mesh (per-chip pool slices; "
                        "requires --tp > 1)")
    parser.add_argument("--max-queue", type=int, default=None,
                        help="admission queue bound; submits past it "
                        "refuse with 429 backpressure")
    parser.add_argument("--priority", type=int, default=0,
                        help="priority of the offline requests (higher "
                        "admits first)")
    parser.add_argument("--deadline-s", type=float, default=None,
                        help="per-request deadline in seconds from submit "
                        "(expired requests evict cleanly)")
    parser.add_argument("--pretrained", default=None, metavar="DIR",
                        help="converted checkpoint dir (models/hf_convert); "
                        "random init otherwise")
    parser.add_argument("--http-port", type=int, default=None,
                        help="serve an HTTP endpoint on this port instead "
                        "of running the offline batch")
    args = parser.parse_args(argv)

    from ..utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from ..models.registry import get_model
    from .api import generate_many, serve_http, throughput_stats
    from .engine import ServeEngine
    from .scheduler import Request

    bundle = get_model(args.model_name, dtype=jnp.float32)
    # a forced 'flash' the compiled kernel cannot take raises here
    from ..utils.logging import print_device_line
    from .kv_pages import resolve_attend_for

    print_device_line("attend", resolve_attend_for(
        bundle.config, args.attend_impl, args.page_size), cache.directory)
    tokenizer = None
    if args.prompt or args.http_port is not None:
        try:
            from ..data import get_tokenizer

            tokenizer = get_tokenizer(args.model_name)
        except Exception:
            if args.prompt:
                raise
    if args.pretrained:
        from ..models.hf_convert import load_pretrained
        from ..parallel import make_mesh, make_plan

        plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
        shapes = jax.eval_shape(
            lambda: bundle.init(bundle.config, jax.random.key(0)))
        shardings = plan.param_shardings(
            bundle.param_logical_axes(bundle.config), shapes)
        params = load_pretrained(bundle, shardings, args.pretrained)
    else:
        params = bundle.init(bundle.config, jax.random.key(args.seed))

    plan = None
    if args.tp > 1:
        from ..parallel import make_mesh, make_plan

        plan = make_plan("tp", make_mesh(tp=args.tp,
                                         devices=jax.devices()[:args.tp]))
    elif args.shard_kv:
        raise SystemExit("--shard-kv needs a tp mesh: pass --tp > 1")
    speculate = None
    if args.speculate == "ngram":
        speculate = "ngram"
    elif args.speculate == "draft":
        from .engine import resolve_context_bounds
        from .spec import DraftModelDrafter

        if args.draft_model is None:
            raise SystemExit("--speculate draft needs --draft-model NAME")
        draft_bundle = get_model(args.draft_model, dtype=jnp.float32)
        if args.draft_pretrained:
            from ..models.hf_convert import load_pretrained
            from ..parallel import make_mesh, make_plan

            dplan = make_plan("single",
                              make_mesh(devices=jax.devices()[:1]))
            dshapes = jax.eval_shape(lambda: draft_bundle.init(
                draft_bundle.config, jax.random.key(0)))
            dshard = dplan.param_shardings(
                draft_bundle.param_logical_axes(draft_bundle.config),
                dshapes)
            draft_params = load_pretrained(draft_bundle, dshard,
                                           args.draft_pretrained)
        else:
            draft_params = draft_bundle.init(draft_bundle.config,
                                             jax.random.key(args.seed + 1))
        target_len = resolve_context_bounds(
            bundle.config, args.max_len, args.page_size)[0]
        speculate = DraftModelDrafter(
            draft_bundle, draft_params, n_slots=args.n_slots,
            max_len=target_len, k=args.spec_k, page_size=args.page_size,
            # drafts are guesses at the target's draws — keep the
            # drafter on the engine's attend family so self-draft
            # acceptance doesn't eat cross-family 1e-5 drift
            attend_impl=args.attend_impl)
    common = dict(n_slots=args.n_slots, page_size=args.page_size,
                  n_pages=args.n_pages, max_len=args.max_len,
                  prefill_chunk=args.prefill_chunk,
                  prefix_cache=False if args.no_prefix_cache else None,
                  attend_impl=args.attend_impl, plan=plan,
                  shard_kv=args.shard_kv, max_queue=args.max_queue,
                  speculate=speculate, spec_k=args.spec_k,
                  kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype)
    if args.replicas > 1 and args.disagg:
        raise SystemExit("--replicas fronts ServeEngine replicas; combine "
                         "with --disagg per replica is future work")
    if args.replicas > 1:
        from .router import local_fleet

        engine = local_fleet(bundle, params, args.replicas, **common)
        report = {"replicas": args.replicas,
                  **engine.replicas["r0"].engine.kv_report()}
        programs = engine.replicas["r0"].engine.programs
    elif args.disagg:
        from .disagg import DisaggEngine

        engine = DisaggEngine(bundle, params,
                              n_prefill_slots=args.prefill_slots,
                              transport=args.transport, **common)
        report = engine.kv_report()
        programs = engine.programs
    else:
        engine = ServeEngine(bundle, params, **common)
        report = engine.kv_report()
        programs = engine.programs
    out = {"kv_report": report}
    if args.weight_dtype is not None:
        # price what --weight-dtype bought: storage + publish/swap payload
        from .engine import build_weight_report

        out["weight_report"] = build_weight_report(programs)
    print(json.dumps(out))

    if args.http_port is not None:
        import signal

        server, worker = serve_http(engine, port=args.http_port,
                                    tokenizer=tokenizer)
        print(json.dumps({"serving": f"http://127.0.0.1:{args.http_port}",
                          "endpoints": ["/generate", "/healthz", "/readyz"]}))
        stop = threading.Event()

        def on_sigterm(signum, frame):
            stop.set()

        signal.signal(signal.SIGTERM, on_sigterm)
        try:
            while not stop.wait(timeout=1.0):
                pass
            # graceful drain: refuse new work (clients see structured
            # 503 + Retry-After), finish everything in flight, THEN exit
            # — a SIGTERM'd replica loses no accepted request
            print(json.dumps({"draining": True}))
            worker.stop(drain=True)
            server.shutdown()
        except KeyboardInterrupt:
            server.shutdown()
            worker.stop()
        return

    prompts = [[int(t) for t in ids.split(",")] for ids in args.prompt_ids]
    for text in args.prompt:
        ids = tokenizer(text)["input_ids"]
        if ids and isinstance(ids[0], list):
            ids = ids[0]
        prompts.append(ids)
    if not prompts:
        raise SystemExit("pass at least one --prompt-ids / --prompt "
                         "(or --http-port for the online endpoint)")
    requests = [Request(prompt_ids=p, max_new_tokens=args.steps,
                        temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed + i,
                        eos_id=args.eos_id, priority=args.priority,
                        deadline_s=args.deadline_s)
                for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    results = generate_many(engine, requests)
    wall = time.perf_counter() - t0
    for res in results:
        line = {"request_id": res.request_id,
                "finish_reason": res.finish_reason,
                "latency_s": round(res.latency_s, 4),
                "token_ids": res.token_ids}
        if tokenizer is not None:
            line["text"] = tokenizer.decode(res.token_ids)
        print(json.dumps(line))
    print(json.dumps({"stats": throughput_stats(results, wall, engine)}))
    cache.print_line()


if __name__ == "__main__":
    main()
