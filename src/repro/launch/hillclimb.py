import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # 512 host placeholders, never a TPU

"""Perf hillclimb driver (§Perf): the three chosen cells, one iteration per
invocation step, each a (hypothesis -> change -> re-lower -> measure) cycle.

    PYTHONPATH=src python -m repro.launch.hillclimb [iteration ...]

Iterations (see EXPERIMENTS.md §Perf for hypotheses and outcomes):
    flexvec-1   corpus_all rules    (score on 256 chips, not 16)
    flexvec-2   + bf16 corpus       (halve the scoring stream)
    flexvec-3   + MMR-in-VMEM       (Pallas kernel pool residency)
    qwen3-1     serve_weights rules (EPxTP resident weights for decode)
    granite-1   remat_policy=dots   (stop recomputing matmuls in bwd)
    granite-2   remat off           (flops floor; memory measured)

Writes reports/perf/<name>.json (same schema as the dry-run cells).
"""

import dataclasses
import json
import sys
from pathlib import Path

PERF_DIR = Path(__file__).resolve().parents[3] / "reports" / "perf"


def _save(name: str, out: dict) -> None:
    PERF_DIR.mkdir(parents=True, exist_ok=True)
    (PERF_DIR / f"{name}.json").write_text(json.dumps(out, indent=2, default=str))
    print(f"[{name}] bottleneck={out['bottleneck']} "
          f"t_comp={out['t_compute_s']:.4g}s t_mem={out['t_memory_s']:.4g}s "
          f"t_coll={out['t_collective_s']:.4g}s "
          f"useful={out.get('useful_flops_ratio')} "
          f"frac={out['roofline_fraction']:.5f}", flush=True)


def flexvec_iters(which: str) -> None:
    import jax.numpy as jnp

    from repro.configs.flexvec import FlexvecArch
    from repro.launch.dryrun import run_cell

    if which == "flexvec-1":
        out = run_cell("flexvec", "corpus_1m", False, "corpus_all",
                       arch_obj=FlexvecArch())
    elif which == "flexvec-2":
        out = run_cell("flexvec", "corpus_1m", False, "corpus_all",
                       arch_obj=FlexvecArch(dtype=jnp.bfloat16))
    elif which == "flexvec-3":
        out = run_cell("flexvec", "corpus_1m", False, "corpus_all",
                       arch_obj=FlexvecArch(dtype=jnp.bfloat16, mmr_vmem=True))
    elif which == "flexvec-4":
        out = run_cell("flexvec", "corpus_1m", False, "corpus_all",
                       arch_obj=FlexvecArch(dtype=jnp.bfloat16, mmr_vmem=True,
                                            two_stage=True))
    elif which == "flexvec-6":
        arch = FlexvecArch(dtype=jnp.bfloat16, mmr_vmem=True, two_stage=True)
        arch.mmr_shards = 16
        out = run_cell("flexvec", "corpus_1m", False, "corpus_all",
                       arch_obj=arch)
    else:
        raise KeyError(which)
    _save(which, out)


def qwen3_iters(which: str) -> None:
    import dataclasses as dc

    from repro.configs import get_arch
    from repro.configs.lm import LMArch
    from repro.launch.dryrun import run_cell

    if which == "qwen3-1":
        out = run_cell("qwen3-moe-235b-a22b", "decode_32k", False, "serve_weights")
    elif which == "qwen3-2":
        base = get_arch("qwen3-moe-235b-a22b")
        cfg = dc.replace(base.cfg, moe=dc.replace(base.cfg.moe, decode_group=8))
        variant = LMArch("qwen3-moe-235b-a22b", base.source, cfg, base.smoke_cfg)
        out = run_cell("qwen3-moe-235b-a22b", "decode_32k", False,
                       "serve_weights", arch_obj=variant)
    else:
        raise KeyError(which)
    _save(which, out)


def granite_iters(which: str) -> None:
    from repro.configs.lm import LMArch
    from repro.configs import get_arch
    from repro.launch.dryrun import run_cell

    base = get_arch("granite-34b")
    if which == "granite-1":
        cfg = dataclasses.replace(base.cfg, remat_policy="dots")
    elif which == "granite-2":
        cfg = dataclasses.replace(base.cfg, remat=False)
    else:
        raise KeyError(which)
    variant = LMArch("granite-34b", base.source, cfg, base.smoke_cfg)
    out = run_cell("granite-34b", "train_4k", False, "default",
                   arch_obj=variant)
    _save(which, out)


def flexvec_scale(which: str) -> None:
    """Beyond-paper scale: the 67M-chunk corpus with every flexvec
    optimization, single- and multi-pod (EXPERIMENTS.md §Perf extras)."""
    import jax.numpy as jnp

    from repro.configs.flexvec import FlexvecArch
    from repro.launch.dryrun import run_cell

    arch = FlexvecArch(dtype=jnp.bfloat16, mmr_vmem=True, two_stage=True)
    arch.mmr_shards = 16
    out = run_cell("flexvec", "corpus_67m", which == "flexvec-67m-multipod",
                   "corpus_all", arch_obj=arch)
    _save(which, out)


RUNNERS = {
    "flexvec-67m": flexvec_scale, "flexvec-67m-multipod": flexvec_scale,
    "flexvec-1": flexvec_iters, "flexvec-2": flexvec_iters,
    "flexvec-3": flexvec_iters, "flexvec-4": flexvec_iters,
    "flexvec-6": flexvec_iters,
    "qwen3-1": qwen3_iters, "qwen3-2": qwen3_iters,
    "granite-1": granite_iters, "granite-2": granite_iters,
}


def main() -> None:
    want = sys.argv[1:] or list(RUNNERS)
    for name in want:
        RUNNERS[name](name)


if __name__ == "__main__":
    main()
