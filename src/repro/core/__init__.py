from repro.core.systems import (
    ComputeSystem, JSCC_SYSTEMS, JSCC_BY_NAME, TPU_SYSTEMS, ALL_SYSTEMS,
    KNL, BROADWELL, SKYLAKE, CASCADE_LAKE, CORI_SYSTEMS, CORI_HASWELL,
    CORI_KNL,
)
from repro.core.workload_model import (
    JobProfile, NPB_PROFILES, NPB_NODES, NPB_CORES, npb_tables,
    predict_runtime, predict_energy, predict_phases, energy_coefficient,
)
from repro.core.profiles import ProfileStore, k_auto
from repro.core.policy import (
    Policy, register_policy, make_policy, policy_names, parse_policy_spec,
    parse_queue_spec, select_batched,
    EXPLORATIONS, FEASIBILITIES, OBJECTIVES, QUEUES,
)
from repro.core.algorithm import select_system, MODES
from repro.core.result import SimResult, CampaignResult
from repro.core.engine import Scheduler
from repro.core.simulator import (
    SimConfig, FaultConfig, Workload, make_npb_workload,
    simulate_jax, simulate_py, sweep_k, run_campaign,
)
from repro.core import energy
