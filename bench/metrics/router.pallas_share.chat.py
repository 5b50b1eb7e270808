"""Router: share of one decode step's GEMM weight bytes that the Router
sent to Pallas, in %.  The shapes and decisions are the program's own
(repro.obs.ROUTES, at the decode batch M = slots); a per-layer shape
counts once per layer and per trace-time call."""


def read(run):
    layer = {tuple(g) for g in run.family.layer_gemms(run.shape)}
    pallas = total = 0.0
    for (op, _letter, _trans, dims), (calls, decision) in run.routes.items():
        if op != "matmul" or tuple(dims[:-2]) != (run.slots, 1):
            continue
        k, n = dims[-2], dims[-1]
        w = k * n * calls * (run.shape.n_layers if (k, n) in layer else 1)
        total += w
        pallas += w if decision.use_pallas else 0.0
    return 100.0 * pallas / total if total else None
