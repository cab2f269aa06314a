"""Share of the rows given to the expert GEMM kernel that are real, in
percent: the rows routed to the held experts over the traced window
(the program's ``ServeStats.moe_rows``) over the rows of the non-empty
blocks they fill (``moe_blocks`` times the family's
``GMM_BLOCK_ROWS``).  Nothing where the program keeps no such counter
or no block was filled."""


def read(ctx):
    rows_per_block = getattr(ctx.family, "GMM_BLOCK_ROWS", None)
    if rows_per_block is None or "moe_blocks" not in ctx.c1:
        return None
    blocks = ctx.delta("moe_blocks")
    if not blocks:
        return None
    return 100.0 * ctx.delta("moe_rows") / (blocks * rows_per_block)
