"""The tiny size the CPU tests run cells at."""
TINY_ORDERS = 3000


def tiny() -> dict:
    from chipbench.data.tpch_lineitem import base_lineitems

    return {"orders": TINY_ORDERS, "lineitems": base_lineitems(TINY_ORDERS),
            "parts": 2000, "suppliers": 100}
