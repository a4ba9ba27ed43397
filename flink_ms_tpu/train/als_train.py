"""ALS training CLI — TPU-native counterpart of ``ALSImpl``
(``flink-als/src/main/scala/de/tub/it4bi/ALSImpl.scala``).

Accepts the reference's flag inventory (SURVEY.md Appendix A) and writes the
same ``id,U|I,f1;f2;...`` model rows, so downstream tools (mean-vector job,
producer/consumer, clients) interoperate with files from either framework.

Flags beyond the reference (TPU-native surface):
  --implicit true      confidence-weighted implicit-feedback ALS (Hu, Koren,
                       Volinsky: c = 1 + alpha * r over play or click counts)
  --alpha 40.0         implicit confidence scale
  --devices N          mesh size (defaults to all visible devices; the
                       reference's --blocks maps to Flink's internal blocking
                       and is accepted — blocking here always equals the mesh)
  --profileDir DIR     write an XLA profiler trace of the fit (TensorBoard)

On a TPU the fit prints one ``[als] assembly:`` line when its sweep is traced:
per side, whether the solve is ``materialised`` or ``per chunk`` (chosen
from the bytes of the side's normal equations and the device's memory;
``FLINK_MS_ALS_FUSED=0|1`` forces it), how many buckets run the assembly
kernel and, last, the dtype the factors are exchanged in.  The ``[ALS]``
report line ends with the same two facts on any backend, e.g. ``exchange
bfloat16, einsum pair`` (a TPU's answer to the default) or ``exchange
float32, einsum pair`` (a CPU's).

``--temporaryPath`` (reference: stage loop intermediates to disk,
ALSImpl.scala:42-44) switches the training loop from one fused XLA program
to per-iteration steps with the factors materialized to disk at every
iteration boundary — and resumes from the latest snapshot on restart
(training checkpoint/resume, SURVEY.md §5).  A copy of the final factors is
also staged under that path.  A snapshot is resumed only by a run of the
same ratings, configuration and starting point; the ratings' identity is
theirs alone, whatever order the file lists them in.  ONE RETRAIN, ONCE:
ratings that name a (user, item) pair more than once (a log with re-rates)
had, until PR 52, an identity that depended on how the host's sort left two
such ratings, so a snapshot of such ratings written before PR 52 is not
resumed and the run starts from iteration 0; ratings of distinct pairs
resume as before.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from ..core import formats as F
from ..core.params import Params, field_delimiter_from
from ..ops.als import ALSConfig, ALSModel, als_fit, exchange_report, rmse
from ..obs.tracing import host_report, phase_report
from ..parallel.distributed import is_primary, maybe_init_distributed
from ..parallel.mesh import compile_report, mesh_for_blocks
from ..utils import profiling


def run(params: Params) -> ALSModel | None:
    if not params.has("input"):
        print("Use --input to specify file input.")
        return None

    # the mesh first: a host with no chip fails by the device rule before
    # the ratings file is parsed, not after.
    # --blocks larger than the device count is legal in the reference (more
    # blocks than slots).  The blocked-ALS solve is exact per row, so any
    # logical block count partitions onto the D device blocks without
    # changing the result; multi-process runs always span every device
    maybe_init_distributed(params)
    mesh = mesh_for_blocks(params.get_int("blocks"), params.get_int("devices"))

    delim = field_delimiter_from(params)
    users, items, ratings = F.read_ratings(
        params.get_required("input"),
        field_delimiter=delim,
        ignore_first_line=params.get_bool("ignoreFirstLine", True),
    )

    config = ALSConfig(
        num_factors=params.get_int("numFactors", 10),
        iterations=params.get_int("iterations", 10),
        lambda_=params.get_float("lambda", 0.9),
        seed=params.get_int("seed", 42),
        implicit=params.get_bool("implicit", False),
        alpha=params.get_float("alpha", 40.0),
    )

    # get_required raises loudly on a present-but-valueless flag
    tmp = (
        params.get_required("temporaryPath").rstrip("/")
        if params.has("temporaryPath")
        else None
    )
    if tmp == "":  # "--temporaryPath /" (or all slashes) is not a usable dir
        raise ValueError("--temporaryPath must name a directory, got a bare '/'")
    t0 = time.time()
    step_timer = profiling.StepTimer("als-iteration") if tmp else None
    with profiling.trace(params.get("profileDir")):
        model = als_fit(
            users, items, ratings, config, mesh,
            temporary_path=tmp,
            step_timer=step_timer,
        )
    train_s = time.time() - t0
    if step_timer is not None and step_timer.durations_s:
        print(step_timer.summary())
    print(
        f"[ALS] model-training: {len(users)} ratings, "
        f"{len(model.user_ids)} users x {len(model.item_ids)} items, "
        f"k={config.num_factors}, {config.iterations} iters, "
        f"{mesh.devices.size} device(s), {train_s:.2f}s "
        f"({train_s / max(config.iterations, 1):.3f} s/iter), "
        f"train RMSE={rmse(model, users, items, ratings):.4f}; "
        f"{exchange_report(config, mesh)}"
    )
    print(f"[ALS] {compile_report()}")
    print(f"[phases] {phase_report()}")
    print(f"[host] {host_report()}")

    if not is_primary():  # one process materializes job output
        return model

    if tmp:
        F.write_als_model(f"{tmp}/userFactors", model.user_ids, F.USER, model.user_factors)
        F.write_als_model(f"{tmp}/itemFactors", model.item_ids, F.ITEM, model.item_factors)

    if params.has("itemFactors") and params.has("userFactors"):
        F.write_als_model(
            params.get_required("itemFactors"), model.item_ids, F.ITEM, model.item_factors
        )
        F.write_als_model(
            params.get_required("userFactors"), model.user_ids, F.USER, model.user_factors
        )
    else:
        print(
            "Printing results to stdout. Use --itemFactors and --userFactors "
            "to specify output locations."
        )
        print("==== USER FACTORS ====")
        for id_, row in zip(model.user_ids, model.user_factors):
            print(F.format_als_row(id_, F.USER, row))
        print("==== ITEM FACTORS ====")
        for id_, row in zip(model.item_ids, model.item_factors):
            print(F.format_als_row(id_, F.ITEM, row))
    return model


def main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
