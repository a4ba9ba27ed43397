"""SVM training CLI — TPU-native counterpart of ``SVMImpl``
(``flink-svm/src/main/scala/de/tub/it4bi/SVMImpl.scala``).

Reference flag surface preserved (SURVEY.md Appendix A), including the
``--iteration`` singular-form quirk (SVMImpl.scala:26 — Appendix C #1;
``--iterations`` is also accepted here as an alias): ``--training`` (req),
``--blocks`` (10), ``--iteration`` (10), ``--partition`` bool, ``--range``
(1000), ``--output``.  Output rows are 1-based ``featureIndex,weight`` or
range-partitioned ``bucket,idx:w;...`` (SVMImpl.scala:33-46).

TPU-native extras surface FlinkML's hidden CoCoA knobs [dep]:
``--localIterations`` (default: one full local pass per round),
``--regularization`` (1.0), ``--stepsize`` (1.0), ``--seed``, ``--devices``,
``--profileDir`` (XLA profiler trace of the fit).
"""

from __future__ import annotations

import sys
import time

from ..core import formats as F
from ..core.params import Params
from ..obs.tracing import host_report, phase_report
from ..ops.svm import (SVMConfig, SVMModel, layout_report,
                       prepare_svm_blocked, svm_fit)
from ..parallel.distributed import is_primary, maybe_init_distributed
from ..parallel.mesh import compile_report, mesh_for_blocks
from ..utils import profiling


def run(params: Params) -> SVMModel:
    training_path = params.get_required("training")
    # the mesh first: a host with no chip fails by the device rule before
    # the training file is parsed, not after
    maybe_init_distributed(params)
    blocks = params.get_int("blocks", 10)
    # blocks = K logical SDCA chains; the mesh spans min(K, devices) (all
    # devices in multi-process runs), and the kernel stacks ceil(K/D)
    # chains per device when K exceeds the device count
    mesh = mesh_for_blocks(blocks, params.get_int("devices"))
    data = F.read_libsvm(training_path)

    iterations = params.get_int("iteration", params.get_int("iterations", 10))
    problem = prepare_svm_blocked(
        data, blocks, seed=params.get_int("seed", 0)
    )
    local_iters = params.get_int("localIterations", problem.rows_per_block)
    config = SVMConfig(
        iterations=iterations,
        local_iterations=local_iters,
        regularization=params.get_float("regularization", 1.0),
        stepsize=params.get_float("stepsize", 1.0),
        seed=params.get_int("seed", 0),
        mode=params.get("mode", "avg"),
        # CoCoA+ smoothing: unset = provably safe gamma*K; values in
        # [1, gamma*K) are the aggressive sparse-data regime (ops/svm.py)
        sigma_prime=params.get_float("sigmaPrime"),
    )

    t0 = time.time()
    with profiling.trace(params.get("profileDir")):
        model = svm_fit(data, config, mesh, problem=problem)
    train_s = time.time() - t0
    print(
        f"[SVM] model-fitting: {data.n_examples} examples x "
        f"{data.n_features} features, {iterations} rounds x {local_iters} "
        f"local steps, {mesh.devices.size} device(s), {train_s:.2f}s, "
        f"hinge+reg objective="
        f"{model.hinge_loss(data, config.regularization):.6f}"
    )
    print(f"[SVM] {layout_report()}; {compile_report()}")
    print(f"[phases] {phase_report()}")
    print(f"[host] {host_report()}")

    if not is_primary():  # one process materializes job output
        return model

    if params.get_bool("partition"):
        rows = F.format_svm_range_rows(model.weights, params.get_int("range", 1000))
    else:
        rows = F.format_svm_flat_rows(model.weights)

    if params.has("output"):
        F.write_lines(params.get_required("output"), rows)
    else:
        print("Printing result to stdout. Use --output to specify output path.")
        for row in rows:
            print(row)
    return model


def main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
