"""repro.train — decentralized training loop substrate."""
from .trainer import (  # noqa: F401
    Features, TrainState, batch_spec_tree, build_train_step, bus_layout_for,
    gossip_round_step, init_state, make_gossip_schedule, make_group_plans,
    make_topology, prepend_agent_axis, resolve_features, resolve_group_specs,
    shard_local_edm_update, state_specs, step_consensus, use_overlap,
    use_packed_bus, use_wire,
)
from . import checkpoint  # noqa: F401
