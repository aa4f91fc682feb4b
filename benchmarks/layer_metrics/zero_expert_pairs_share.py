"""``zero_expert_pairs_share``: of the token-expert pairs a window's decode
steps routed (rows x top-k in every expert layer, held here or not), the
share that fell on identity experts, router outputs past the real experts
whose pair adds ``w x`` and costs no matmul, in percent. A program without
``paddle_generation_zero_expert_pairs_total`` (before PR 40), or a session
whose router has no identity experts (the counter never moves, so a window's
delta does not hold it), has nothing to read.
"""


def read(facts):
    zero = facts.counters.get("paddle_generation_zero_expert_pairs_total")
    routed = facts.counters.get("paddle_generation_routed_pairs_total")
    if zero is None or not routed:
        return None
    return 100.0 * zero / routed
