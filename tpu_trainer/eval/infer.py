"""Inference CLI: checkpoint → jitted generate → text.

Re-design of the reference's ``src/eval/infer.py`` (SURVEY.md C27): Orbax
restore instead of pickle (no ``TrainingConfig`` unpickle shim, no
``weights_only`` fallback — reference ``infer.py:19-21,53-56``), a jitted
sampling loop, and the model config read from the checkpoint's own metadata
(``--model_size`` only needed for consolidated files). All four sizes load,
including ``xl`` — the reference CLI caps at ``large`` while its FSDP trainer
can train ``xl`` (SURVEY.md §2.1 b13).

Usage::

    python -m tpu_trainer.eval.infer --checkpoint checkpoints/step_00001000 \
        --prompt "Once upon a time" --max_new_tokens 100 --temperature 0.8 --top_k 50
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import generate_bucketed, generate_kv
from tpu_trainer.utils.checkpoint import latest_checkpoint, restore_params
from tpu_trainer.utils.tokenizer import get_tokenizer


def force_cpu():
    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Generate text from a checkpoint")
    p.add_argument("--checkpoint", required=True,
                   help="step dir, checkpoint root (picks latest), or .msgpack")
    p.add_argument("--model_size", default=None,
                   choices=["small", "medium", "large", "xl"],
                   help="only needed for consolidated .msgpack files")
    p.add_argument("--prompt", default="Once upon a time")
    p.add_argument("--max_new_tokens", type=int, default=100)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top_k", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tokenizer", default="gpt2")
    p.add_argument("--device", default=None, choices=[None, "cpu", "tpu"],
                   help="cpu forces the host platform")
    p.add_argument("--no_kv_cache", action="store_true",
                   help="use the windowed full-forward sampler (the "
                        "reference's O(S^2) semantics) instead of the "
                        "KV-cached decoder")
    p.add_argument("--prompt_file", default=None,
                   help="file with one prompt per line: decoded as ONE "
                        "ragged batch (per-row lengths; KV cache path)")
    p.add_argument("--serve", action="store_true",
                   help="decode through the continuous-batching serving "
                        "engine (paged KV cache) instead of generate_kv; "
                        "each prompt becomes one request, sampled from its "
                        "own per-request stream (seed + row index)")
    p.add_argument("--serve_batch", type=int, default=8,
                   help="serving engine slot batch (with --serve)")
    p.add_argument("--serve_block_size", type=int, default=16,
                   help="paged KV cache block size (with --serve)")
    p.add_argument("--spec", default="off",
                   choices=["off", "ngram", "draft"],
                   help="speculative decoding proposer (with --serve); "
                        "greedy output is bit-identical either way")
    p.add_argument("--spec_k", type=int, default=4,
                   help="max draft tokens per verify step (with --spec)")
    p.add_argument("--spec_draft_layers", type=int, default=1,
                   help="checkpoint layers sliced into the draft model "
                        "(with --spec draft)")
    p.add_argument("--record_trace", default=None, metavar="OUT.JSONL",
                   help="append each served prompt/response as a "
                        "serve_bench-replayable trace record (with --serve)")
    p.add_argument("--mesh_data", type=int, default=1,
                   help="shard batch rows over a data mesh axis")
    p.add_argument("--mesh_tensor", type=int, default=1,
                   help="Megatron-style tensor-parallel decode")
    args = p.parse_args(argv)

    if args.device == "cpu":
        force_cpu()
    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    path = args.checkpoint
    resolved = latest_checkpoint(path)
    if resolved is not None:
        path = resolved
    import os
    if not os.path.exists(path):
        p.error(f"checkpoint not found: {path}")
    if os.path.isdir(path) and not os.path.exists(os.path.join(path, "meta.json")):
        p.error(f"no checkpoint (meta.json) at {path}; pass a step dir, a "
                f"checkpoint root containing step_* dirs, or a .msgpack file")
    params, config = restore_params(path)
    if config is None:
        if args.model_size is None:
            p.error("--model_size is required for consolidated checkpoints")
        config = GPTConfig.preset(args.model_size)
    # Sampling is deterministic-eval: no dropout.
    import dataclasses
    config = dataclasses.replace(config, dropout=0.0, attention_dropout=0.0)
    if args.mesh_tensor > 1 and config.fused_projections:
        # TP shards the q/k/v kernels along the axis the fusion
        # concatenates (same gate as Trainer.__init__).
        config = dataclasses.replace(config, fused_projections=False)

    tokenizer = get_tokenizer(args.tokenizer)
    if args.prompt_file:
        with open(args.prompt_file) as f:
            prompts = [ln.rstrip("\n") for ln in f if ln.strip()]
        if not prompts:
            p.error(f"no prompts in {args.prompt_file}")
    else:
        prompts = [args.prompt]
    eos = min(tokenizer.eos_token_id, config.vocab_size - 1)
    rows = [tokenizer.encode(pr) or [eos] for pr in prompts]
    top = max(max(r) for r in rows)
    if top >= config.vocab_size:
        p.error(
            f"prompt tokenizes to id {top} but the checkpoint's model has "
            f"vocab_size {config.vocab_size} — tokenizer/model mismatch "
            f"(tokenizer: {tokenizer.name})"
        )
    lens = [len(r) for r in rows]
    width = max(lens)
    input_ids = jnp.asarray(
        [r + [0] * (width - len(r)) for r in rows], jnp.int32
    )
    prompt_lens = (jnp.asarray(lens, jnp.int32)
                   if len(set(lens)) > 1 else None)

    # KV-cached decode (O(S) per token) when the result fits the cache;
    # the windowed full-forward path handles overflow and --no_kv_cache.
    fits = width + args.max_new_tokens <= config.max_seq_len
    use_kv = fits and not args.no_kv_cache
    if prompt_lens is not None and not use_kv:
        p.error("ragged multi-prompt decode needs the KV path: shorten "
                "--max_new_tokens to fit max_seq_len, or drop --no_kv_cache")

    if args.record_trace and not args.serve:
        p.error("--record_trace records served requests; add --serve")
    if args.spec != "off" and not args.serve:
        p.error("--spec is a serving-engine feature; add --serve")

    if args.serve:
        # Serving-engine escape hatch: same checkpoint/tokenizer plumbing,
        # but each prompt is an independent request with its own sampling
        # stream (seed = --seed + row). temperature 0 reproduces
        # generate_kv's greedy output exactly; stochastic draws come from
        # per-request streams, so they differ from the shared-rng batch
        # sampler by construction.
        if args.no_kv_cache:
            p.error("--serve is the paged KV path; drop --no_kv_cache")
        if args.mesh_data * args.mesh_tensor > 1:
            p.error("--serve does not compose with mesh sharding yet")
        if not fits:
            p.error("prompt + --max_new_tokens exceeds max_seq_len")
        from tpu_trainer.serving import (
            Request, SamplingParams, ServingEngine, draft_from_target,
        )

        draft_params = draft_config = None
        if args.spec == "draft":
            if args.spec_draft_layers >= config.num_layers:
                p.error(f"--spec_draft_layers {args.spec_draft_layers} must "
                        f"be < the checkpoint's {config.num_layers} layers")
            draft_params, draft_config = draft_from_target(
                params, config, args.spec_draft_layers)
        engine = ServingEngine(
            params, config,
            max_batch=min(len(rows), args.serve_batch),
            block_size=args.serve_block_size,
            spec=args.spec, spec_k=args.spec_k,
            draft_params=draft_params, draft_config=draft_config,
        )
        reqs = [
            Request(rid=i, prompt=list(r),
                    max_new_tokens=args.max_new_tokens,
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k,
                                            seed=args.seed + i))
            for i, r in enumerate(rows)
        ]
        finished = engine.run(reqs, time_mode="steps")
        for r in finished:
            print(tokenizer.decode(r.prompt + r.generated))
        if args.record_trace:
            # Replayable serve_bench records (benchmarks/serve_bench.py
            # --trace): real token ids ride along in prompt_tokens so a
            # replay model with a covering vocab feeds the true prompt;
            # loaders without them fall back to seeded synthesis at the
            # same lengths. Text fields are provenance, ignored on load.
            import json as _json

            with open(args.record_trace, "a") as fh:
                for i, r in enumerate(finished):
                    fh.write(_json.dumps({
                        "prompt_len": len(r.prompt),
                        "max_new": r.max_new_tokens,
                        "arrival_time": r.arrival_time,
                        "temperature": r.sampling.temperature,
                        "top_k": r.sampling.top_k,
                        "top_p": r.sampling.top_p,
                        "seed": r.sampling.seed,
                        "prompt_tokens": [int(t) for t in r.prompt],
                        "tokenizer": tokenizer.name,
                        "prompt_text": prompts[i],
                        "response_text": tokenizer.decode(r.generated),
                    }) + "\n")
        return 0

    n_shards = args.mesh_data * args.mesh_tensor
    if n_shards > 1 and not use_kv:
        p.error("sharded decode uses the KV path: shorten --max_new_tokens "
                "to fit max_seq_len, or drop --no_kv_cache")
    if n_shards > 1:
        # Sharded decode: batch rows over `data`, Megatron TP over
        # `tensor` (the training param rules reused verbatim — decode is
        # just another consumer of the same layout).
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_trainer.parallel import sharding as shard_lib
        from tpu_trainer.parallel.mesh import (
            DATA_AXIS, MeshConfig, make_mesh,
        )

        if len(prompts) % args.mesh_data != 0:
            p.error(f"{len(prompts)} prompts not divisible by "
                    f"--mesh_data {args.mesh_data}")
        mesh = make_mesh(MeshConfig(data=args.mesh_data, fsdp=1,
                                    tensor=args.mesh_tensor))
        params = jax.device_put(
            params,
            shard_lib.to_shardings(
                shard_lib.params_specs(params, mesh, "replicated"), mesh
            ),
        )
        row_sharding = NamedSharding(mesh, P(DATA_AXIS))
        input_ids = jax.device_put(
            input_ids, NamedSharding(mesh, P(DATA_AXIS, None))
        )
        if prompt_lens is not None:
            prompt_lens = jax.device_put(prompt_lens, row_sharding)

    sampler = generate_kv if use_kv else generate_bucketed
    kwargs = dict(config=config, max_new_tokens=args.max_new_tokens,
                  temperature=args.temperature, top_k=args.top_k)
    if use_kv and prompt_lens is not None:
        kwargs["prompt_lens"] = prompt_lens
    if n_shards > 1:
        out = jax.jit(
            lambda pp, rr, ii: generate_kv(pp, rr, ii, **kwargs)
        )(params, jax.random.PRNGKey(args.seed), input_ids)
    else:
        out = sampler(
            params, jax.random.PRNGKey(args.seed), input_ids, **kwargs
        )
    out = jax.device_get(out)
    for i, L in enumerate(lens):
        n_real = L + args.max_new_tokens if use_kv else out.shape[1]
        text = tokenizer.decode(list(out[i, :n_real]))
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
