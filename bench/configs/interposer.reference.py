"""Plain reference of the interposer design-space model (arXiv:2403.04189).

Row by row, in straightforward array code, what one design point of the
grid scores: the network's loss chain, counts and bandwidth per topology
(SPRINT and SPACX buses, the switched Tree and TRINE), then laser, trimming
and switch power, latency and energy under one workload's traffic.  The
formulas follow the paper's analytical methodology; the device constants
come from the configuration file.  It imports nothing of the program.

`xp` is the array namespace (numpy on the host, jax.numpy on the chip) and
`dtype` the precision: float64 is the reference, float32 the control.
"""

import numpy as np

# a value within this relative distance of a multiple of 1/2 is taken as on
# it before a ceil, floor or round: the model's counts are decided at exact
# ties (400e9 / 80e9 = 5 subnetworks), which a last-bit error must not flip
TIE_RTOL = 1e-9

OBJECTIVES = ("latency_s", "energy_j", "power_w")


def snap(v, xp):
    h = xp.round(v * 2.0) * 0.5
    return xp.where(xp.abs(v - h) <= TIE_RTOL * xp.abs(h), h, v)


def ceil_log2(v, xp):
    """ceil(log2 v) for 1 <= v <= 2**31, by counting the powers of two below
    v (exact in any precision)."""
    v = snap(v, xp)
    return sum((v > 2.0 ** k).astype(v.dtype) for k in range(32))


def decode_rows(cfg_grid, start, stop, xp, dtype):
    """Columns of flat rows [start, stop): C order over (topology, *axes),
    every other column at its base value.  Returns (cols, topology id)."""
    shape = [len(cfg_grid["topologies"])] + [len(v) for v in
                                             cfg_grid["axes"].values()]
    idx = np.arange(start, stop, dtype=np.int64)
    digits = np.unravel_index(idx, shape)
    cols = {k: xp.full(idx.size, v, dtype) for k, v in cfg_grid["base"].items()}
    for i, (name, vals) in enumerate(cfg_grid["axes"].items()):
        cols[name] = xp.asarray(np.asarray(vals, np.float64)[digits[1 + i]],
                                dtype)
    return cols, digits[0]


def _bus_derate(writers, xp):
    return 1.0 / (1.0 + 0.05 * xp.maximum(0.0, writers - 1.0))


def sprint(c, xp):
    n_wg = 8.0
    g, lam = c["n_gateways"], c["n_lambda"]
    loss = ((g - 1) * 2 * lam * c["mr.through_loss_db"]
            + 4 * c["interposer_side_cm"] * c["wg.propagation_loss_db_per_cm"]
            + c["mr.drop_loss_db"] + c["mr.modulation_loss_db"])
    raw = n_wg * lam * c["modulation_rate_bps"]
    one = xp.ones_like(loss)
    return dict(loss=loss, n_lambda=n_wg * lam,
                n_mr=(g + c["n_mem_chiplets"]) * 2 * lam * 2, n_mzi=0 * one,
                n_stages=0 * one, bw=raw * _bus_derate(g, xp),
                per_transfer_s=12e-9 * one, n_banks=n_wg * one)


def spacx(c, xp):
    cluster = 8.0
    g, lam = c["n_gateways"], c["n_lambda"]
    n_cl = xp.floor(snap(g / cluster, xp))
    loss = ((cluster - 1) * 2 * lam * c["mr.through_loss_db"]
            + 1.5 * c["interposer_side_cm"] * c["wg.propagation_loss_db_per_cm"]
            + c["mr.drop_loss_db"] + c["mr.modulation_loss_db"])
    raw = n_cl * lam * c["modulation_rate_bps"]
    one = xp.ones_like(loss)
    return dict(loss=loss, n_lambda=n_cl * lam,
                n_mr=g * 2 * lam + c["n_mem_chiplets"] * 2 * lam * n_cl,
                n_mzi=0 * one, n_stages=0 * one,
                bw=raw * _bus_derate(cluster * one, xp),
                per_transfer_s=8e-9 * one, n_banks=n_cl)


def tree(c, xp):
    g, lam = c["n_gateways"], c["n_lambda"]
    stages = ceil_log2(g, xp)
    loss = (stages * c["mzi.insertion_loss_db"]
            + c["interposer_side_cm"] / 2 * c["wg.propagation_loss_db_per_cm"]
            + c["mr.drop_loss_db"] + c["mr.modulation_loss_db"])
    return dict(loss=loss, n_lambda=lam,
                n_mr=(g + c["n_mem_chiplets"]) * 2 * lam, n_mzi=g - 1,
                n_stages=stages, bw=lam * c["modulation_rate_bps"],
                per_transfer_s=stages * c["mzi.switch_time_s"],
                n_banks=xp.ones_like(loss))


def trine(c, xp):
    """K tree subnetworks; K = the power of two nearest (in log space) to
    the memory bandwidth over one waveguide's, at most one per gateway."""
    g, lam = c["n_gateways"], c["n_lambda"]
    mem_bw = c["n_mem_chiplets"] * c["mem_bw_bytes_per_s"] * 8.0
    wg_bw = lam * c["modulation_rate_bps"]
    k = xp.maximum(1.0, xp.ceil(snap(mem_bw / wg_bw, xp)))
    k = xp.minimum(2.0 ** xp.round(xp.log2(k)), g)
    per = xp.maximum(1.0, xp.floor(snap(g / k, xp)))
    stages = xp.maximum(1.0, ceil_log2(per, xp))
    loss = (stages * c["mzi.insertion_loss_db"]
            + c["interposer_side_cm"] / 3 * c["wg.propagation_loss_db_per_cm"]
            + c["mr.drop_loss_db"] + c["mr.modulation_loss_db"])
    return dict(loss=loss, n_lambda=k * lam,
                n_mr=(g + c["n_mem_chiplets"] * k) * 2 * lam,
                n_mzi=k * (per - 1), n_stages=stages,
                bw=xp.minimum(k * wg_bw, mem_bw),
                per_transfer_s=stages * c["mzi.switch_time_s"], n_banks=k)


TOPOLOGIES = {"sprint": sprint, "spacx": spacx, "tree": tree, "trine": trine}


def network_metrics(c, net, total_bits, n_transfers, xp, frac=1.0):
    """Power, latency and energy of photonic networks with a fraction
    `frac` of their wavelengths, laser banks and gateways lit."""
    frac = xp.clip(frac, 1e-3, 1.0)
    lit_lambda = xp.maximum(1.0, xp.round(snap(net["n_lambda"] * frac, xp)))
    lit_banks = xp.maximum(1.0, xp.round(snap(net["n_banks"] * frac, xp)))
    p_tx_dbm = (c["pd.sensitivity_dbm"] + c["laser.power_margin_db"]
                + net["loss"] + c["laser.coupling_loss_db"])
    laser = (lit_lambda * 1e-3 * 10.0 ** (p_tx_dbm / 10.0)
             / c["laser.wall_plug_efficiency"]
             + lit_banks * c["laser.bank_overhead_w"])
    static = (laser + net["n_mr"] * c["mr.tuning_power_w"] * frac
              + net["n_mzi"] * c["mzi.static_power_w"] * frac)
    latency = (total_bits / (net["bw"] * frac)
               + n_transfers * net["per_transfer_s"])
    dyn = total_bits * (c["driver.energy_per_bit_j"]
                        + c["driver.serdes_energy_per_bit_j"]
                        + c["pd.energy_per_bit_j"])
    switch = n_transfers * net["n_stages"] * c["mzi.switch_energy_j"]
    return {"latency_s": latency,
            "energy_j": static * latency + dyn + switch,
            "power_w": static + (dyn + switch) / latency}


def score_rows(cfg_grid, traffic, start, stop, xp=np, dtype=np.float64):
    """(stop - start, 3) objectives of flat rows [start, stop)."""
    cols, topo = decode_rows(cfg_grid, start, stop, xp, dtype)
    total_bits = 8.0 * (traffic["bytes_read"] + traffic["bytes_written"])
    out = None
    for t, name in enumerate(cfg_grid["topologies"]):
        m = network_metrics(cols, TOPOLOGIES[name](cols, xp), total_bits,
                            traffic["n_transfers"], xp)
        pts = xp.stack([m[k] for k in OBJECTIVES], -1)
        sel = xp.asarray(topo == t)[:, None]
        out = pts if out is None else xp.where(sel, pts, out)
    return out


def score_joint_rows(cfg_grid, layers, mix, accel, start, stop, xp=np,
                     dtype=np.float64):
    """(stop - start, 3) objectives of one chiplet mix on network rows
    [start, stop) under every layer of the workload.

    Each layer's dots are split over the chiplets in proportion to their
    throughput (a unit of vector size V takes ceil(L / V) passes for a dot
    of length L); compute energy counts the best chiplet's wavelength slots.
    Gateways are lit just enough to carry the layer's traffic at the rate it
    is computed, in steps of one per 8 wavelengths.  Network and memory
    transfers overlap compute: a layer takes the longest of the three."""
    cols, topo = decode_rows(cfg_grid, start, stop, xp, dtype)
    net = None
    for t, name in enumerate(cfg_grid["topologies"]):
        f = TOPOLOGIES[name](cols, xp)
        sel = xp.asarray(topo == t)
        net = f if net is None else {k: xp.where(sel, f[k], net[k]) for k in f}
    mem_bw = cols["n_mem_chiplets"] * cols["mem_bw_bytes_per_s"]

    def col(k):
        return xp.asarray(np.asarray([l[k] for l in layers], np.float64), dtype)

    dots, n_dots = col("dot_length"), col("n_dots")
    nbytes = col("weight_bytes") + col("in_bytes") + col("out_bytes")
    thr, slots = 0.0, None
    for chip in mix:
        passes = xp.ceil(snap(dots / chip["vector_size"], xp))
        thr = thr + chip["n_units"] * accel["mac_rate_hz"] / passes
        s = passes * chip["vector_size"]
        slots = s if slots is None else xp.minimum(slots, s)
    compute_s = n_dots / thr                                    # (L,)
    compute_e = xp.sum(n_dots * slots) * accel["lambda_slot_energy_j"]

    c2 = {k: v[:, None] for k, v in cols.items()}
    net2 = {k: v[:, None] for k, v in net.items()}
    n_gw = xp.maximum(1.0, xp.floor(snap(net2["n_lambda"] / 8.0, xp)))
    max_bw = net2["bw"] / 8.0
    need = xp.clip((nbytes / xp.maximum(compute_s, 1e-12))[None, :] / max_bw,
                   0.0, 1.0)
    frac = xp.maximum(1.0, xp.ceil(snap(need * n_gw, xp))) / n_gw
    m = network_metrics(c2, net2, 8.0 * nbytes[None, :],
                        accel["transfers_per_layer"], xp, frac)
    layer_s = xp.maximum(xp.maximum(compute_s[None, :], m["latency_s"]),
                         nbytes[None, :] / mem_bw[:, None])
    latency = xp.sum(layer_s, -1)
    energy = compute_e + xp.sum(m["energy_j"], -1)
    return xp.stack([latency, energy, energy / latency], -1)


def pareto_front(points, indices, block=4096):
    """Rows of `points` (n, m), lower is better, that no other row
    dominates (no worse in every objective, better in one; exact duplicates
    do not dominate each other).  Lexicographic order means a row can only
    be dominated by one before it, so one pass against the front so far is
    enough.  Returns (front points, their indices)."""
    points = np.asarray(points, np.float64)
    order = np.lexsort(points.T[::-1])
    pts, idx = points[order], np.asarray(indices)[order]
    front_p = np.empty((0, pts.shape[1]))
    front_i = np.empty(0, np.int64)
    for s in range(0, len(pts), block):
        p, i = pts[s:s + block], idx[s:s + block]
        keep = ~_dominated(p, front_p)
        p, i = p[keep], i[keep]
        le = (p[None, :, :] <= p[:, None, :]).all(-1)   # [a, b]: b <= a
        ne = (p[None, :, :] != p[:, None, :]).any(-1)
        keep = ~(le & ne).any(1)
        front_p = np.concatenate([front_p, p[keep]])
        front_i = np.concatenate([front_i, i[keep]])
    return front_p, front_i


def _dominated(p, front):
    if not len(front) or not len(p):
        return np.zeros(len(p), bool)
    le = (front[None, :, :] <= p[:, None, :]).all(-1)
    ne = (front[None, :, :] != p[:, None, :]).any(-1)
    return (le & ne).any(1)
