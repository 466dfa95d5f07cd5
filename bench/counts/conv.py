"""Operations and bytes of a ReBranch CNN, from its configuration's sizes.

A ReBranch conv site (paper Fig. 7-8) is an int8 ROM trunk conv beside
a float branch: 1x1 compress (C_in -> C_in/D), KxK core (-> C_out/U),
1x1 decompress (-> C_out).  Counts are what the algorithm needs: each
multiply-accumulate is 2 operations, the compress runs once per input
pixel, and bytes count each input, weight and output once.
"""

from __future__ import annotations


def sites(body: dict) -> list[tuple[str, int, int, int, int]]:
    """``(site, k, c_in, c_out, hw)`` for every ROM conv site of a
    DarkNet-style plan (``backbone`` with "M" pools, then ``head``);
    ``hw`` is the site's output resolution (stride 1, SAME)."""
    out, c_in, hw = [], 3, body["input_size"]
    ci = 0
    for item in body["backbone"]:
        if item == "M":
            hw //= 2
            continue
        c, k = item
        out.append((f"convs.{ci}", k, c_in, c, hw))
        c_in, ci = c, ci + 1
    for hi, (c, k) in enumerate(body["head"]):
        out.append((f"head.{hi}", k, c_in, c, hw))
        c_in = c
    return out


def site_work(body: dict, site, batch: int) -> dict:
    """One site's work for ``batch`` images: int8 trunk operations,
    float branch operations (compress, core, decompress) and the bytes
    of the fused trunk+compress kernel (f32 input and trunk output,
    int8 weights, f32 compress)."""
    _, k, c_in, c_out, hw = site
    d, u = body["d_ratio"], body["u_ratio"]
    c_c, c_u = max(1, c_in // d), max(1, c_out // u)
    px = batch * hw * hw
    trunk = 2 * px * k * k * c_in * c_out
    compress = 2 * px * c_in * c_c
    core = 2 * px * k * k * c_c * c_u
    decompress = 2 * px * c_u * c_out
    kernel_bytes = (4 * px * c_in + k * k * c_in * c_out + 4 * c_out
                    + 4 * c_in * c_c + 4 * px * c_out)
    return {"int8_ops": trunk, "compress_ops": compress,
            "branch_ops": compress + core + decompress,
            "kernel_bytes": kernel_bytes}


def forward_work(body: dict, batch: int) -> dict:
    """A whole forward: the ROM sites' int8 trunk operations, every
    float operation (branches and the 1x1 predictor), and the fused
    kernels' share (int8 trunk plus compress, with their bytes)."""
    tot = {"int8_ops": 0, "float_ops": 0, "kernel_float_ops": 0,
           "kernel_bytes": 0}
    c_last, hw = 0, 0
    for s in sites(body):
        w = site_work(body, s, batch)
        tot["int8_ops"] += w["int8_ops"]
        tot["float_ops"] += w["branch_ops"]
        tot["kernel_float_ops"] += w["compress_ops"]
        tot["kernel_bytes"] += w["kernel_bytes"]
        c_last, hw = s[3], s[4]
    n_out = body["head_anchors"] * (5 + body["head_classes"])
    tot["float_ops"] += 2 * batch * hw * hw * c_last * n_out
    return tot
