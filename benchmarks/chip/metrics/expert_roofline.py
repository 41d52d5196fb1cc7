"""expert_roofline: the covenant_experts calls' share of their roofline, in
%: the least time the chip could take for the expert layer's work
(``counts_experts``) over the device time of every op in the harness's
``gemm.experts`` scope, the two grouped GEMMs (``grouped_matmul``) and the
wrapper's routing, sort, gather, SwiGLU and combine around them."""


def read(r):
    spent = sum(s for label, s in r.ops_s.items()
                if label.split(":")[0] == "gemm.experts")
    if "experts" not in r.work or spent <= 0:
        return None
    return 100.0 * r.work["experts"]["roofline_s"] / spent
