"""Tokens routed to the busiest held expert over the mean of the held
experts, on the held-out batch at the window's last logged step, the worst
MoE layer (a program counter the cell's log hook reads)."""


def read(ctx):
    return ctx["counts"].get("moe_imbalance")
