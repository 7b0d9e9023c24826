"""LiGO — the paper's primary contribution: a learned linear growth operator
that initialises a large transformer from a smaller pretrained one.

Growth executes through the compiled :class:`repro.core.plan.GrowthPlan`
(expander caching, leaf batching, fused Pallas kernel on TPU). The per-leaf
walk ``apply_ligo_reference`` is the tests' correctness oracle; no
production path calls it."""
from repro.core.ligo import (apply_ligo, apply_ligo_reference,
                             count_ligo_params, gamma_expand,
                             init_ligo_params, interp_pattern, stack_pattern)
from repro.core.grow import TRACE_COUNTS, grow, ligo_loss, train_ligo
from repro.core.plan import (GrowthPlan, compose_chain, compose_ligo,
                             place_operator, plan_for)
from repro.core.grow_cache import (CacheGrowthError, grow_decode_state,
                                   is_lossless_operator)
from repro.core.upcycle import upcycle_operator
from repro.core import grow_cache, operators, spec, upcycle

__all__ = ["apply_ligo", "apply_ligo_reference", "init_ligo_params",
           "count_ligo_params", "gamma_expand", "stack_pattern",
           "interp_pattern", "grow", "ligo_loss", "train_ligo", "GrowthPlan",
           "plan_for", "compose_ligo", "compose_chain", "place_operator",
           "TRACE_COUNTS", "operators", "spec", "grow_cache", "upcycle",
           "upcycle_operator", "CacheGrowthError", "grow_decode_state",
           "is_lossless_operator"]
