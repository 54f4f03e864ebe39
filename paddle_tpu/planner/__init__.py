"""Auto-parallelism planner: cost-model-driven search over
mesh x DistributedStrategy x comms settings.

Closes the loop from "we can price a config" (``analysis/costs.py`` +
``analysis/memory.py``) to "we pick the config": enumerate every mesh
factorization of the device count crossed with the strategy knobs the
fleet exposes (gradient sync mode, int8 quantized comms, bucketed
overlap, ZeRO-1, AMP), price each candidate's compute / comm / bubble
legs under a :class:`~paddle_tpu.analysis.costs.DeviceProfile`, reject
what cannot fit HBM (op-attributed), and rank the rest by predicted
step seconds.

CLI: ``python -m paddle_tpu.analysis --plan --devices 256 --device
v5e`` prints the ranked table; ``--json-out`` writes a plan document
``DistributedStrategy.from_plan`` applies directly.
"""
from .plan import ParallelPlan, MESH_AXIS_ORDER
from .candidates import enumerate_plans, tp_compatible
from .pricing import (PricedPlan, ProgramBase, build_base, price_plan)
from .search import PlanSearchResult, plan_search, price_composition

__all__ = [
    "ParallelPlan", "MESH_AXIS_ORDER", "enumerate_plans",
    "tp_compatible", "PricedPlan", "ProgramBase", "build_base",
    "price_plan", "PlanSearchResult", "plan_search",
    "price_composition",
]
