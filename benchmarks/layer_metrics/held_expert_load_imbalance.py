"""``held_expert_load_imbalance``: the busiest held expert's token-expert
pairs over the mean load of a held expert, over the window's decode steps
and expert layers (1 is a perfect balance), for a chip that holds a share of
a layer's experts (``n_routed_experts`` of the configuration as it is run;
the router is wider). The routing counters count held experts since PR 31;
a program without ``paddle_generation_routed_pairs_total`` (before it), or
a configuration without ``n_routed_experts``, has nothing to read.
"""


def read(facts):
    held = facts.cfg.get("n_routed_experts")
    assigned = facts.counters.get(
        "paddle_generation_expert_assignments_total")
    busiest = facts.counters.get("paddle_generation_expert_max_load_total")
    if not held or not assigned or busiest is None or \
            "paddle_generation_routed_pairs_total" not in facts.counters:
        return None
    return busiest * held / assigned
