"""``expert_load_imbalance``: the busiest expert's token-expert pairs over
the mean load of an expert, over the window's decode steps and expert layers
(1 is a perfect balance). From the program's routing counters; a program
without them (before PR 27) has nothing to read.
"""


def read(facts):
    assigned = facts.counters.get(
        "paddle_generation_expert_assignments_total")
    busiest = facts.counters.get("paddle_generation_expert_max_load_total")
    if not assigned or busiest is None:
        return None
    return busiest * facts.cfg["num_experts"] / assigned
