"""TPC-C streams (paper section 4.4): NewOrder + Payment, 50/50.

Keys are laid out from the Warehouse table down (warehouse, district,
customer, stock); the partition of a key is its warehouse. Payment
writes its warehouse (the hot record), district and customer (15% at a
remote warehouse) and looks the customer up by last name 60% of the
time (OLLP); NewOrder reads its warehouse, writes its district and ten
stock rows (1% of items remote).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.gen import Common
from bench.reference.data import MODE_READ, MODE_WRITE, Workload
from bench.reference.lockgrant import KEY_SENTINEL


@dataclasses.dataclass(frozen=True)
class Params(Common):
    num_warehouses: int = 16
    districts_per_wh: int = 10
    customers_per_district: int = 3000
    stock_per_wh: int = 100_000
    remote_payment_frac: float = 0.15
    remote_item_prob: float = 0.01  # per NewOrder item => ~10% remote txns
    payment_by_name_frac: float = 0.60
    ollp_miss_prob: float = 0.01


# the program's generator gives the same arrays at each of these
CASES = [
    dict(num_txns=2048, num_warehouses=4, seed=0),
    dict(num_txns=2048, num_warehouses=16, seed=3_000_000_001),
]


def tpcc_layout(cfg: Params):
    """Key-space layout rooted at the Warehouse table."""
    W, D, C, S = (
        cfg.num_warehouses,
        cfg.districts_per_wh,
        cfg.customers_per_district,
        cfg.stock_per_wh,
    )
    wh_base = 0
    di_base = W
    cu_base = di_base + W * D
    st_base = cu_base + W * D * C
    total = st_base + W * S
    return wh_base, di_base, cu_base, st_base, total


def generate(cfg: Params) -> Workload:
    rng = np.random.default_rng(cfg.seed)
    n = cfg.num_txns
    W, D, C, S = (
        cfg.num_warehouses,
        cfg.districts_per_wh,
        cfg.customers_per_district,
        cfg.stock_per_wh,
    )
    wh_base, di_base, cu_base, st_base, total = tpcc_layout(cfg)

    K = 12  # NewOrder: 1 wh read + 1 district write + 10 stock writes
    keys = np.full((n, K), int(KEY_SENTINEL), np.int64)
    modes = np.zeros((n, K), np.int32)
    part = np.zeros((n, K), np.int32)  # warehouse id per key
    nkeys = np.zeros(n, np.int32)
    exec_ops = np.zeros(n, np.int32)
    ollp = np.zeros(n, bool)
    ollp_miss = np.zeros(n, bool)

    is_payment = rng.random(n) < 0.5
    w = rng.integers(0, W, size=n)
    d = rng.integers(0, D, size=n)

    # ---- Payment: W(write, HOT), D(write), C(write; 15% remote wh) ----
    pay = np.where(is_payment)[0]
    npay = len(pay)
    cw = w[pay].copy()
    remote = rng.random(npay) < cfg.remote_payment_frac
    if W > 1:
        cw_r = rng.integers(0, W, size=npay)
        # remote customer warehouse must differ from home warehouse
        for _ in range(8):
            clash = remote & (cw_r == w[pay])
            if not clash.any():
                break
            cw_r = np.where(clash, rng.integers(0, W, size=npay), cw_r)
        cw = np.where(remote, cw_r, cw)
    cd = rng.integers(0, D, size=npay)
    cc = rng.integers(0, C, size=npay)
    keys[pay, 0] = wh_base + w[pay]
    keys[pay, 1] = di_base + w[pay] * D + d[pay]
    keys[pay, 2] = cu_base + (cw * D + cd) * C + cc
    modes[pay, 0:3] = MODE_WRITE
    part[pay, 0] = w[pay]
    part[pay, 1] = w[pay]
    part[pay, 2] = cw
    nkeys[pay] = 3
    exec_ops[pay] = 3
    byname = rng.random(npay) < cfg.payment_by_name_frac
    ollp[pay] = byname
    ollp_miss[pay] = byname & (rng.random(npay) < cfg.ollp_miss_prob)

    # ---- NewOrder: W(read), D(write, next_o_id), 10x Stock(write) ----
    new = np.where(~is_payment)[0]
    nnew = len(new)
    keys[new, 0] = wh_base + w[new]
    modes[new, 0] = MODE_READ
    part[new, 0] = w[new]
    keys[new, 1] = di_base + w[new] * D + d[new]
    modes[new, 1] = MODE_WRITE
    part[new, 1] = w[new]
    items = 10
    sw = np.repeat(w[new][:, None], items, axis=1)
    if W > 1:
        rem = rng.random((nnew, items)) < cfg.remote_item_prob
        sw_r = rng.integers(0, W, size=(nnew, items))
        for _ in range(8):
            clash = rem & (sw_r == sw)
            if not clash.any():
                break
            sw_r = np.where(clash, rng.integers(0, W, size=(nnew, items)), sw_r)
        sw = np.where(rem, sw_r, sw)
    si = rng.integers(0, S, size=(nnew, items))
    keys[new, 2 : 2 + items] = st_base + sw * S + si
    modes[new, 2 : 2 + items] = MODE_WRITE
    part[new, 2 : 2 + items] = sw
    nkeys[new] = 2 + items
    # +10 Item reads execute without locks (read-only table, paper §4.4)
    exec_ops[new] = 2 + items + items

    return Workload(
        keys=keys.astype(np.int32),
        modes=modes,
        nkeys=nkeys,
        part=part.astype(np.int32),
        exec_ops=exec_ops,
        ollp=ollp,
        ollp_miss=ollp_miss,
        num_records=int(total),
        batch_epoch=cfg.batch_epoch,
    )
