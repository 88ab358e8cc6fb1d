"""Minimal ONNX loader + JAX executor.

The reference links the ONNX Runtime C library (surrealml/core — `ort`).
This environment has neither onnxruntime nor the `onnx` python package, so
the ModelProto protobuf is decoded directly (protobuf wire format is
simple: varint tags + length-delimited fields) and the graph executes
through jax.numpy on XLA's CPU backend, in the serving process. It does
NOT share the accelerator: a chip belongs to one process, and that
process is the DeviceRunner (`_pin_host_backend`).

Covered operator set (the sklearn/torch-exported MLP/linear family the
reference's surrealml tooling produces): MatMul, Gemm, Add, Sub, Mul, Div,
Relu, Sigmoid, Tanh, Softmax, Identity, Constant, Flatten, Reshape, Cast,
Neg, Exp, Sqrt, Pow, Clip, LeakyRelu, Concat, ReduceMean, ReduceSum.
"""

from __future__ import annotations

import struct
from typing import Any, Optional

import numpy as np

from surrealdb_tpu.err import SdbError


# ---------------------------------------------------------------------------
# protobuf wire decoding
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int):
    out = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:  # varint
            v, i = _varint(buf, i)
        elif wt == 1:  # 64-bit
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:  # length-delimited
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:  # 32-bit
            v = buf[i:i + 4]
            i += 4
        else:
            raise SdbError(f"unsupported protobuf wire type {wt}")
        yield fno, wt, v


def _packed_varints(buf: bytes):
    out = []
    i = 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


_DTYPES = {
    1: np.float32, 2: np.uint8, 3: np.int8, 5: np.int16, 6: np.int32,
    7: np.int64, 9: np.bool_, 10: np.float16, 11: np.float64,
    12: np.uint32, 13: np.uint64,
}


def _tensor(buf: bytes) -> tuple[str, np.ndarray]:
    dims = []
    dtype = 1
    raw = None
    floats = []
    ints = []
    name = ""
    for fno, wt, v in _fields(buf):
        if fno == 1:  # dims
            if wt == 0:
                dims.append(v)
            else:
                dims.extend(_packed_varints(v))
        elif fno == 2:
            dtype = v
        elif fno == 4:  # float_data (packed)
            floats.extend(struct.unpack(f"<{len(v) // 4}f", v))
        elif fno == 7:  # int64_data
            if wt == 0:
                ints.append(v)
            else:
                ints.extend(_packed_varints(v))
        elif fno == 8:
            name = v.decode()
        elif fno == 9:
            raw = v
    np_dt = _DTYPES.get(dtype, np.float32)
    if raw is not None:
        arr = np.frombuffer(raw, dtype=np_dt)
    elif floats:
        arr = np.asarray(floats, dtype=np.float32)
    elif ints:
        arr = np.asarray(ints, dtype=np.int64)
    else:
        arr = np.zeros(0, np_dt)
    if dims:
        arr = arr.reshape(dims)
    return name, arr


def _attr(buf: bytes):
    name = ""
    val: Any = None
    for fno, wt, v in _fields(buf):
        if fno == 1:
            name = v.decode()
        elif fno == 2:  # f
            val = struct.unpack("<f", v)[0]
        elif fno == 3:  # i
            val = v - (1 << 64) if v >= (1 << 63) else v
        elif fno == 4:  # s
            val = v.decode(errors="replace")
        elif fno == 5:  # t
            val = _tensor(v)[1]
        elif fno == 7:  # floats
            val = list(struct.unpack(f"<{len(v) // 4}f", v))
        elif fno == 8:  # ints (packed or repeated)
            if wt == 0:
                val = (val or []) + [v]
            else:
                val = _packed_varints(v)
    return name, val


class OnnxNode:
    __slots__ = ("op", "inputs", "outputs", "attrs")

    def __init__(self, op, inputs, outputs, attrs):
        self.op = op
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs


class OnnxGraph:
    """Decoded ONNX graph: nodes in topological (file) order, initializer
    weights, and the input/output value names."""

    __slots__ = ("nodes", "weights", "inputs", "outputs")

    def __init__(self):
        self.nodes: list[OnnxNode] = []
        self.weights: dict[str, np.ndarray] = {}
        self.inputs: list[str] = []
        self.outputs: list[str] = []

    @classmethod
    def parse(cls, model_bytes: bytes) -> "OnnxGraph":
        graph_buf = None
        for fno, _wt, v in _fields(model_bytes):
            if fno == 7:  # ModelProto.graph
                graph_buf = v
        if graph_buf is None:
            raise SdbError("not an ONNX model: no graph found")
        g = cls()
        for fno, _wt, v in _fields(graph_buf):
            if fno == 1:  # node
                op = ""
                ins: list[str] = []
                outs: list[str] = []
                attrs: dict[str, Any] = {}
                for f2, _w2, v2 in _fields(v):
                    if f2 == 1:
                        ins.append(v2.decode())
                    elif f2 == 2:
                        outs.append(v2.decode())
                    elif f2 == 4:
                        op = v2.decode()
                    elif f2 == 5:
                        an, av = _attr(v2)
                        attrs[an] = av
                g.nodes.append(OnnxNode(op, ins, outs, attrs))
            elif fno == 5:  # initializer
                name, arr = _tensor(v)
                g.weights[name] = arr
            elif fno in (11, 12):  # input / output ValueInfoProto
                vname = ""
                for f2, _w2, v2 in _fields(v):
                    if f2 == 1:
                        vname = v2.decode()
                        break
                if fno == 11:
                    g.inputs.append(vname)
                else:
                    g.outputs.append(vname)
        # graph inputs exclude initializers (weights list as inputs too)
        g.inputs = [x for x in g.inputs if x not in g.weights]
        return g


# ---------------------------------------------------------------------------
# JAX execution
# ---------------------------------------------------------------------------

def _pin_host_backend():
    """Hold this process's jax to the CPU backend BEFORE its first
    backend init. `run_graph` executes on a query thread of the serving
    process; left to its default, jax would open the TPU here — taking
    the chip from the DeviceRunner that owns it, or failing because the
    runner already has it. A config update (not the environment: the
    runner inherits that) placed before any array is created is the
    whole pin (repeating it is free). `SURREAL_DEVICE=inline` runs
    device ops in this process by design and is left alone."""
    from surrealdb_tpu.device import get_supervisor

    if get_supervisor().mode != "inline":
        import jax

        jax.config.update("jax_platforms", "cpu")



def _softmax(x, axis):
    import jax.numpy as jnp

    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=axis, keepdims=True)


def _spatial_pads(a, nsp: int):
    """ONNX pads [b1..bn, e1..en] -> [(b1,e1)...]; SAME_UPPER handled by
    the caller via explicit output shapes when auto_pad is set."""
    pads = a.get("pads")
    if pads is None:
        return [(0, 0)] * nsp
    return [(int(pads[i]), int(pads[i + nsp])) for i in range(nsp)]


def _conv(ins, a):
    """ONNX Conv on NCHW/NCW layouts via lax.conv_general_dilated (the
    MXU-friendly convolution primitive; reference links ONNX Runtime)."""
    import jax.numpy as jnp
    from jax import lax

    x, w = ins[0], ins[1]
    nsp = x.ndim - 2
    strides = [int(s) for s in a.get("strides", [1] * nsp)]
    dil = [int(d) for d in a.get("dilations", [1] * nsp)]
    group = int(a.get("group", 1))
    if a.get("auto_pad") in ("SAME_UPPER", "SAME_LOWER"):
        padding = "SAME"
    else:
        padding = _spatial_pads(a, nsp)
    dims = ("NCHW", "OIHW", "NCHW") if nsp == 2 else ("NCH", "OIH", "NCH")
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dil, feature_group_count=group,
        dimension_numbers=dims,
    )
    if len(ins) > 2 and ins[2] is not None:
        b = ins[2]
        shp = [1] * out.ndim
        shp[1] = b.shape[0]
        out = out + b.reshape(shp)
    return out


def _pool(x, a, op):
    """ONNX MaxPool/AveragePool via lax.reduce_window (count_include_pad=0
    semantics for the average: divide by the number of REAL elements)."""
    import jax.numpy as jnp
    from jax import lax

    nsp = x.ndim - 2
    ks = [int(k) for k in a.get("kernel_shape", [1] * nsp)]
    strides = [int(s) for s in a.get("strides", [1] * nsp)]
    pads = _spatial_pads(a, nsp)
    window = (1, 1) + tuple(ks)
    wstr = (1, 1) + tuple(strides)
    wpad = ((0, 0), (0, 0)) + tuple(pads)
    if op == "MaxPool":
        return lax.reduce_window(
            x, -jnp.inf, lax.max, window, wstr, wpad
        )
    sums = lax.reduce_window(x, 0.0, lax.add, window, wstr, wpad)
    if not a.get("count_include_pad") and any(
        p != (0, 0) for p in pads
    ):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, wstr, wpad)
        return sums / counts
    return sums / float(np.prod(ks))


def run_graph(g: OnnxGraph, feed: dict[str, np.ndarray]) -> list:
    """Execute the graph; returns the output arrays (numpy)."""
    _pin_host_backend()
    import jax.numpy as jnp

    env: dict[str, Any] = {k: jnp.asarray(v) for k, v in g.weights.items()}
    for k, v in feed.items():
        env[k] = jnp.asarray(v, dtype=jnp.float32)

    def get(name):
        if name == "":
            return None
        if name not in env:
            raise SdbError(f"ONNX execution: missing tensor '{name}'")
        return env[name]

    for node in g.nodes:
        op = node.op
        a = node.attrs
        ins = [get(x) for x in node.inputs]
        if op == "MatMul":
            out = ins[0] @ ins[1]
        elif op == "Gemm":
            x, w = ins[0], ins[1]
            if a.get("transA"):
                x = x.T
            if a.get("transB"):
                w = w.T
            out = a.get("alpha", 1.0) * (x @ w)
            if len(ins) > 2 and ins[2] is not None:
                out = out + a.get("beta", 1.0) * ins[2]
        elif op == "Add":
            out = ins[0] + ins[1]
        elif op == "Sub":
            out = ins[0] - ins[1]
        elif op == "Mul":
            out = ins[0] * ins[1]
        elif op == "Div":
            out = ins[0] / ins[1]
        elif op == "Relu":
            out = jnp.maximum(ins[0], 0)
        elif op == "LeakyRelu":
            out = jnp.where(ins[0] > 0, ins[0], a.get("alpha", 0.01) * ins[0])
        elif op == "Sigmoid":
            out = 1.0 / (1.0 + jnp.exp(-ins[0]))
        elif op == "Tanh":
            out = jnp.tanh(ins[0])
        elif op == "Softmax":
            out = _softmax(ins[0], a.get("axis", -1))
        elif op in ("Identity", "Cast", "Dropout"):
            out = ins[0]
        elif op == "Constant":
            out = jnp.asarray(a.get("value"))
        elif op == "Flatten":
            ax = a.get("axis", 1)
            shp = ins[0].shape
            lead = int(np.prod(shp[:ax])) if ax else 1
            out = ins[0].reshape(lead, -1)
        elif op == "Reshape":
            shape = [int(x) for x in np.asarray(ins[1]).tolist()]
            out = ins[0].reshape(shape)
        elif op == "Concat":
            out = jnp.concatenate(ins, axis=a.get("axis", 0))
        elif op == "Neg":
            out = -ins[0]
        elif op == "Exp":
            out = jnp.exp(ins[0])
        elif op == "Sqrt":
            out = jnp.sqrt(ins[0])
        elif op == "Pow":
            out = ins[0] ** ins[1]
        elif op == "Clip":
            lo = ins[1] if len(ins) > 1 and ins[1] is not None else None
            hi = ins[2] if len(ins) > 2 and ins[2] is not None else None
            out = jnp.clip(ins[0], lo, hi)
        elif op == "ReduceMean":
            out = jnp.mean(ins[0], axis=tuple(a.get("axes", [])) or None,
                           keepdims=bool(a.get("keepdims", 1)))
        elif op == "ReduceSum":
            out = jnp.sum(ins[0], axis=tuple(a.get("axes", [])) or None,
                          keepdims=bool(a.get("keepdims", 1)))
        elif op == "Transpose":
            perm = a.get("perm")
            out = jnp.transpose(ins[0], axes=perm)
        elif op == "Gather":
            idx = jnp.asarray(ins[1], jnp.int32)
            out = jnp.take(ins[0], idx, axis=a.get("axis", 0))
        elif op == "Squeeze":
            axes = a.get("axes")
            if axes is None and len(ins) > 1 and ins[1] is not None:
                axes = [int(x) for x in np.asarray(ins[1]).tolist()]
            out = (
                jnp.squeeze(ins[0], axis=tuple(axes)) if axes
                else jnp.squeeze(ins[0])
            )
        elif op == "Unsqueeze":
            axes = a.get("axes")
            if axes is None and len(ins) > 1 and ins[1] is not None:
                axes = [int(x) for x in np.asarray(ins[1]).tolist()]
            out = ins[0]
            for ax in sorted(axes or [0]):
                out = jnp.expand_dims(out, int(ax))
        elif op == "Shape":
            out = jnp.asarray(ins[0].shape, jnp.int64)
        elif op == "BatchNormalization":
            x, scale, bias, mean, var = ins[:5]
            eps = a.get("epsilon", 1e-5)
            # stats broadcast over the channel axis (axis 1)
            shp = [1] * x.ndim
            shp[1] = x.shape[1]
            out = (
                (x - mean.reshape(shp))
                / jnp.sqrt(var.reshape(shp) + eps)
                * scale.reshape(shp)
                + bias.reshape(shp)
            )
        elif op == "Conv":
            out = _conv(ins, a)
        elif op in ("MaxPool", "AveragePool"):
            out = _pool(ins[0], a, op)
        elif op == "GlobalAveragePool":
            out = jnp.mean(
                ins[0], axis=tuple(range(2, ins[0].ndim)), keepdims=True
            )
        elif op == "GlobalMaxPool":
            out = jnp.max(
                ins[0], axis=tuple(range(2, ins[0].ndim)), keepdims=True
            )
        else:
            raise SdbError(f"ONNX operator '{op}' is not supported")
        env[node.outputs[0]] = out
        for extra in node.outputs[1:]:
            env[extra] = out

    return [np.asarray(env[o]) for o in g.outputs if o in env]
