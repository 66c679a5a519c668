"""The paper's primary contribution: MRLS topologies, multipass/Polarized
routing, analytic scalability machinery, and collective workloads."""
from .topology import (
    Topology, mrls, fat_tree, oft, dragonfly, dragonfly_plus, rfc, jellyfish,
)
from .routing import (
    bfs_distances, RoutingTables, TableDelta, build_tables, pack_port_masks,
    iter_port_mask_blocks, mask_table_bytes, route_row_words, pack_route_rows,
    polarized_port_mask, route_packet_host, find_corners, POLICIES,
    FUSED_POLICIES, MASK_LAYOUTS, DENSE_MASK_LIMIT, UNREACHABLE,
)
from .failures import FailureEvent, FailureSchedule, canonical_link_ids
from .analytics import (
    Metrics, exact_metrics, theta, cost_links, cost_switches,
    mrls_distance_distribution, mrls_expected_A, mrls_expected_A_star,
    prob_dstar_leq, dstar_thresholds, mrls_design,
)
from .collectives import (
    all2all_rounds, rabenseifner_phases, ring_allreduce_phases,
    recursive_doubling_phases,
    all2all_lower_bound_slots, allreduce_lower_bound_slots,
)

# Canonical topology-family table: the string names the declarative layer
# (``repro.api``) resolves NetworkSpec.family against.  Kept here, next to
# the builders, so adding a topology automatically reaches every driver.
TOPOLOGY_BUILDERS = {
    "mrls": mrls,
    "fat_tree": fat_tree,
    "oft": oft,
    "dragonfly": dragonfly,
    "dragonfly_plus": dragonfly_plus,
    "rfc": rfc,
    "jellyfish": jellyfish,
}

__all__ = [k for k in dir() if not k.startswith("_")]
