"""Share of the rows given to the held experts' grouped products that hold
no routed pair (1 - the pairs routed to held experts / the rows of the
sorted buffers, both as the traced steps counted them), in percent."""


def read(m):
    rows = m.counts.get("moe_rows")
    if not rows:
        return None
    return 100.0 * (1.0 - m.counts["moe_routed"] / rows)
