"""Serving driver: paged-KV-cache decode, standalone or following a trainer.

Demo mode — decode from freshly initialized weights (engine smoke test)::

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --batch 2 --prompt-len 16 --new-tokens 8 --temperature 0.8

Follow mode — the serve side of the train-to-serve loop.  Point it at the
``<ckpt>_ckpts`` directory of a running (or finished) ``launch.train
--compiled --ckpt ... --ckpt-every N`` process::

  PYTHONPATH=src python -m repro.launch.serve --follow /tmp/fl_ckpts

Follow mode reads ``spec.json`` from the checkpoint directory (written by
the trainer before round 0; ``--spec`` overrides), rebuilds the experiment
and the restore template from it, and serves synthetic prompt traffic while
watching the manifest: every newly committed boundary is restored
(fingerprint + treedef validated — ``repro.serve`` package docstring has
the full hand-off contract), scored on held-out loss by the promotion gate,
and hot-swapped into the engine iff it is no worse than what is being
served (``PromotionGate``).  Decode never stops for a swap and the decode
program never recompiles across swaps.  Serving geometry and gate policy
come from the spec's ``serve`` section (``repro.api.ServeSpec``).

Exits printing the promotion log and a machine-readable summary line::

  serve summary: promotions=2 rollbacks=1 tokens=1920 tokens_per_sec=412.3 ...

PRNG discipline (the old driver reused ONE key for params, prompts, and
sampling, and always took the first post-prefill token greedily): every
consumer gets its own split — prompt synthesis draws from a dedicated
traffic stream, the engine's sampling stream is seeded separately, and the
first generated token goes through the same temperature-respecting sampler
as every later one (inside the jitted prefill).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer


def _demo(args) -> None:
    """Standalone decode from fresh weights — no checkpoint directory."""
    from repro.serve import ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    k_params, k_prompts, k_sample = jax.random.split(key, 3)
    params = transformer.init_params(cfg, k_params)

    engine = ServeEngine(
        cfg,
        params,
        batch=args.batch,
        max_seq=args.prompt_len + args.new_tokens,
        page_size=args.page_size,
        temperature=args.temperature,
        seed=int(jax.random.randint(k_sample, (), 0, 2**31 - 1)),
    )
    prompts = jax.random.randint(
        k_prompts, (args.batch, args.prompt_len), 0, cfg.vocab
    )

    t0 = time.time()
    engine.start(prompts)
    print(f"prefill {args.batch}x{args.prompt_len} in {time.time() - t0:.2f}s")
    engine.step(args.new_tokens - 1)
    print(
        f"decoded {args.new_tokens - 1} steps in {engine.decode_seconds:.2f}s "
        f"({engine.tokens_per_sec():.1f} tok/s, "
        f"{engine.decode_cache_entries()} decode compile)"
    )
    print("generated ids:", engine.generated().tolist())


def _load_followed_spec(ckpt_dir: str, spec_path: str, timeout: float):
    """The spec of the run being followed: ``--spec`` wins, else wait for
    the trainer's ``spec.json`` to appear in the checkpoint directory."""
    from repro.api import ExperimentSpec

    if spec_path:
        return ExperimentSpec.load(spec_path)
    path = os.path.join(ckpt_dir, "spec.json")
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() >= deadline:
            raise FileNotFoundError(
                f"no {path} after {timeout:.0f}s — is launch.train running "
                "with --compiled --ckpt --ckpt-every on this directory? "
                "(or pass --spec explicitly)"
            )
        time.sleep(0.1)
    return ExperimentSpec.load(path)


def _follow(args) -> None:
    """Follow a training checkpoint directory: the serve side of the loop."""
    from repro import api
    from repro.checkpoint import CheckpointManager, config_fingerprint
    from repro.serve import (
        CheckpointWatcher,
        PromotionGate,
        ServeEngine,
        ServeSession,
        heldout_batches,
    )

    spec = _load_followed_spec(args.follow, args.spec, args.timeout)
    srv = spec.serve
    built = api.build(spec)
    cfg = built.arch_config
    if cfg is None:
        raise SystemExit(
            "--follow serves zoo runs (TaskSpec.kind='zoo'); the followed "
            f"spec has kind={spec.task.kind!r}"
        )
    template = api.restore_template(spec, built=built)
    manager = CheckpointManager(
        args.follow, fingerprint=config_fingerprint(spec.to_dict())
    )

    # Round-0 weights: the engine starts serving the untrained model and the
    # gate's bar is ITS held-out loss — the first trained boundary promotes
    # iff training helped.
    engine = ServeEngine(
        cfg,
        template.params,
        batch=srv.batch,
        max_seq=srv.max_seq,
        page_size=srv.page_size,
        temperature=args.temperature if args.temperature is not None else srv.temperature,
        seed=spec.execution.seed + 1,
    )
    gate = PromotionGate(
        cfg,
        heldout_batches(
            built.dataset,
            n_batches=srv.eval_batches,
            batch_size=spec.federation.batch_size,
            seed=spec.execution.seed,
        ),
        tolerance=srv.tolerance,
    )
    watcher = CheckpointWatcher(manager, template)

    traffic_key = [jax.random.fold_in(jax.random.PRNGKey(spec.execution.seed), 11)]

    def prompt_fn():
        traffic_key[0], sub = jax.random.split(traffic_key[0])
        return jax.random.randint(sub, (srv.batch, srv.prompt_len), 0, cfg.vocab)

    def on_decision(candidate, promoted):
        rec = gate.log.records[-1]
        print(
            f"boundary step {candidate.step}: "
            f"{'PROMOTE' if promoted else 'ROLLBACK'} ({rec.reason}); "
            f"serving at {engine.tokens_per_sec():.1f} tok/s",
            flush=True,
        )

    print(
        f"following {args.follow} (arch={cfg.name}, horizon="
        f"{spec.federation.rounds} rounds); gate bar (round-0 init) = "
        f"{gate.prime(engine.params):.4f}",
        flush=True,
    )
    session = ServeSession(
        engine,
        watcher,
        gate,
        prompt_fn=prompt_fn,
        decode_steps_per_poll=srv.decode_steps_per_poll,
        final_step=spec.federation.rounds,
        on_decision=on_decision,
    )
    summary = session.run(timeout=args.timeout, poll_timeout=args.poll)
    assert engine.decode_cache_entries() == 1, (
        f"decode recompiled under swaps: {engine.decode_cache_entries()} "
        "jit cache entries (compile-once contract)"
    )
    print(gate.log.render())
    print(summary.render(), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Paged-KV-cache serving: standalone demo, or --follow a "
        "training checkpoint directory with eval-gated hot swaps"
    )
    ap.add_argument(
        "--follow", default="", metavar="CKPT_DIR",
        help="follow this CheckpointManager directory (the <ckpt>_ckpts dir "
        "of launch.train --compiled --ckpt-every): hot-swap each committed "
        "boundary that clears the promotion gate",
    )
    ap.add_argument(
        "--spec", default="",
        help="ExperimentSpec JSON of the followed run (default: wait for "
        "CKPT_DIR/spec.json, which launch.train writes)",
    )
    ap.add_argument(
        "--timeout", type=float, default=120.0,
        help="follow mode: overall serving wall-clock budget (and the wait "
        "budget for spec.json to appear)",
    )
    ap.add_argument(
        "--poll", type=float, default=0.2,
        help="follow mode: manifest poll bound between decode chunks (s)",
    )
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument(
        "--temperature", type=float, default=None,
        help="sampling temperature (demo default 0.0; follow mode defaults "
        "to the spec's serve.temperature)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    if args.follow:
        _follow(args)
    else:
        if args.temperature is None:
            args.temperature = 0.0
        _demo(args)


if __name__ == "__main__":
    main()
