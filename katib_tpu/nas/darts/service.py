"""DARTS suggester — config-only service.

Parity with the reference (``pkg/suggestion/v1beta1/nas/darts/service.py``):
all search happens inside the single trial; the suggester's job is to convert
the NAS operations into a primitive list (``get_search_space`` :102), merge
algorithm settings over defaults (:118-135), validate them (:162), and emit
exactly ONE trial carrying three string parameters: ``algorithm-settings``,
``search-space``, ``num-layers`` (:49-99).
"""

from __future__ import annotations

import json

from katib_tpu.core.types import (
    Experiment,
    ExperimentSpec,
    ParameterAssignment,
    TrialAssignmentSet,
)
from katib_tpu.suggest.base import (
    SearchExhausted,
    Suggester,
    SuggesterError,
    register,
)

from katib_tpu.nas.darts.architect import DartsHyper

DEFAULT_SETTINGS: dict[str, object] = {
    # reference defaults ``darts/service.py:118-135``; the optimizer-side
    # values come from DartsHyper so the trial and service can't drift
    "num_epochs": 50,
    **{
        k: v
        for k, v in DartsHyper._field_defaults.items()
        if k not in ("total_steps", "unrolled")
    },
    "batch_size": 128,
    "init_channels": 16,
    "num_nodes": 4,
    "stem_multiplier": 3,
}

_POSITIVE_INT = {
    "num_epochs", "batch_size", "init_channels", "num_nodes",
    "stem_multiplier", "n_train", "n_test",
    # scan-window of the device-resident step loop (search.py); the
    # camelCase spelling is the Katib-style CR surface, the snake_case
    # the internal one — both validate the same way
    "step_loop_window", "stepLoopWindow",
}
# augment_epochs may be 0 (off, the default); validated separately below
_NON_NEGATIVE_INT = {"augment_epochs"}
_POSITIVE_FLOAT = {
    "w_lr",
    "w_lr_min",
    "w_momentum",
    "w_weight_decay",
    "w_grad_clip",
    "alpha_lr",
    "alpha_weight_decay",
    "augment_lr",
}


def search_space_from_nas_config(nas_config) -> list[str]:
    """Operations -> primitive names (reference ``get_search_space`` :102:
    ``<operation_type>_<k>x<k>`` per filter size; skip_connection bare).
    ``none`` is bare too: the reference's trial appends it to every search
    space itself, here a CR lists it to reach ``DEFAULT_PRIMITIVES``."""
    primitives: list[str] = []
    for op in nas_config.operations:
        if op.operation_type in ("skip_connection", "none"):
            primitives.append(op.operation_type)
            continue
        sizes = []
        for p in op.parameters:
            if p.name == "filter_size" and p.feasible.list:
                sizes = list(p.feasible.list)
        if not sizes:
            raise SuggesterError(
                f"operation {op.operation_type!r} needs a filter_size categorical parameter"
            )
        for k in sizes:
            primitives.append(f"{op.operation_type}_{k}x{k}")
    return primitives


@register("darts")
class DartsSuggester(Suggester):
    @classmethod
    def validate(cls, spec: ExperimentSpec) -> None:
        if spec.nas_config is None or not spec.nas_config.operations:
            raise SuggesterError("darts requires nas_config with operations")
        search_space_from_nas_config(spec.nas_config)
        for name, raw in spec.algorithm.settings.items():
            if name in _POSITIVE_INT or name in _NON_NEGATIVE_INT:
                try:
                    v = int(raw)
                except (TypeError, ValueError):
                    raise SuggesterError(f"{name} must be an integer") from None
                if v <= 0 and name in _POSITIVE_INT:
                    raise SuggesterError(f"{name} must be > 0")
                if v < 0:
                    raise SuggesterError(f"{name} must be >= 0")
            elif name in _POSITIVE_FLOAT:
                try:
                    v = float(raw)
                except (TypeError, ValueError):
                    raise SuggesterError(f"{name} must be a number") from None
                if v < 0:
                    raise SuggesterError(f"{name} must be >= 0")
            elif name == "dataset":
                from katib_tpu.models.data import NAMED_DATASETS

                if str(raw) not in NAMED_DATASETS:
                    # a typo must fail at submission, not after the search
                    raise SuggesterError(
                        f"dataset must be one of {NAMED_DATASETS}, got {raw!r}"
                    )

    def merged_settings(self) -> dict:
        merged = dict(DEFAULT_SETTINGS)
        for k, v in self.spec.algorithm.settings.items():
            merged[k] = v
        return merged

    def get_suggestions(
        self, experiment: Experiment, count: int
    ) -> list[TrialAssignmentSet]:
        if experiment.trials:
            # one search trial per experiment (reference emits exactly one,
            # ``service.py:49``: "DARTS algorithm uses only one trial")
            raise SearchExhausted("darts runs exactly one search trial")
        primitives = search_space_from_nas_config(self.spec.nas_config)
        num_layers = self.spec.nas_config.graph_config.num_layers
        return [
            TrialAssignmentSet(
                assignments=[
                    ParameterAssignment(
                        "algorithm-settings", json.dumps(self.merged_settings())
                    ),
                    ParameterAssignment("search-space", json.dumps(primitives)),
                    ParameterAssignment("num-layers", str(num_layers)),
                ],
                labels={"nas": "darts"},
            )
        ]
