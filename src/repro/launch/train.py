"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch whisper-base \
        [--steps 200] [--global-batch 8] [--seq 448] [--model-parallel 1] \
        [--ckpt /path] [--reduced] [--no-dmd]

Without --reduced the config runs at its published sizes on a (data, model)
mesh over every device JAX finds (--model-parallel sets the model axis);
--reduced runs the same-family shrunk config on one device. SIGTERM
triggers a checkpoint-and-exit (preemption handling); rerunning with the
same --ckpt resumes bit-exactly.
"""
import argparse
import dataclasses


def build(arch: str, *, steps: int, reduced: bool = False, dmd: bool = True,
          global_batch: int = 0, seq: int = 0, ckpt: str = "",
          model_parallel: int = 1, devices=None):
    """(acfg, model, mesh) exactly as the launcher runs them. ``devices``
    (default: all of ``jax.devices()``) places the non-reduced mesh."""
    from repro.configs import get_config, reduced as reduce_model, \
        shape_by_name
    from repro.models.transformer import LanguageModel

    acfg = get_config(arch)
    mc = reduce_model(acfg.model) if reduced else acfg.model
    gb = global_batch or (8 if reduced else
                          shape_by_name("train_4k").global_batch)
    seq = seq or (64 if reduced else 4096)
    acfg = dataclasses.replace(
        acfg, model=mc,
        dmd=dataclasses.replace(acfg.dmd, enabled=dmd,
                                warmup_steps=min(acfg.dmd.warmup_steps,
                                                 steps // 4)),
        train=dataclasses.replace(acfg.train, global_batch=gb, seq_len=seq,
                                  checkpoint_every=50 if ckpt else 0,
                                  checkpoint_dir=ckpt))

    mesh = None
    if not reduced:
        import jax
        from repro.launch.mesh import make_mesh_for_devices
        devices = list(devices if devices is not None else jax.devices())
        mesh = make_mesh_for_devices(len(devices), model_parallel,
                                     devices=devices)

    model = LanguageModel(mc, head_tp=not reduced,
                          chunk_k=min(seq, 1024),
                          remat=acfg.parallel.remat if not reduced
                          else "none",
                          pad_heads_to=(mesh.shape["model"] if mesh is not None
                                        else 0))
    return acfg, model, mesh


def batches(acfg, model, start: int = 0):
    """The launcher's synthetic token stream (a pure function of the step
    index, so a resumed run replays it exactly)."""
    from repro.data.tokens import synthetic_lm_batches

    mc, tc = model.cfg, acfg.train
    return synthetic_lm_batches(
        tc.seed, tc.global_batch, tc.seq_len, mc.vocab_size,
        start_step=start, mrope=bool(mc.mrope_sections),
        frames=(mc.encoder_seq_len, mc.d_model)
        if mc.family == "encdec" else None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--no-dmd", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    args = ap.parse_args()

    from repro.checkpoint import latest_step
    from repro.distributed.sharding import mesh_context
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train import Trainer

    enable_compile_cache()
    acfg, model, mesh = build(
        args.arch, steps=args.steps, reduced=args.reduced,
        dmd=not args.no_dmd, global_batch=args.global_batch, seq=args.seq,
        ckpt=args.ckpt, model_parallel=args.model_parallel)
    tc = acfg.train
    print(f"{args.arch}: {model.param_count()/1e6:.1f}M params, "
          f"dmd={'off' if args.no_dmd else 'on'}, "
          f"batch={tc.global_batch}x{tc.seq_len}")

    def run():
        trainer = Trainer(model, acfg, mesh=mesh,
                          checkpoint_dir=args.ckpt or None)
        start = (latest_step(args.ckpt) or 0) if args.ckpt else 0
        trainer.fit(batches(acfg, model, start), steps=args.steps,
                    log_every=10)

    if mesh is not None:
        with mesh_context(mesh):
            run()
    else:
        run()


if __name__ == "__main__":
    main()
