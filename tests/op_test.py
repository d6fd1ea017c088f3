"""OpTest: numeric-parity harness for single ops.

Re-creates the reference's OpTest methodology (reference:
python/paddle/fluid/tests/unittests/op_test.py:133 — build a one-op program,
run it, compare vs a numpy reference:304; gradient check by central finite
differences:44 vs programmatic grads:418) on the XLA engine.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.core.types import convert_np_dtype_to_dtype_


class OpTest:
    """Subclass and set: op_type, inputs (dict slot->np array or list of
    (name, array)), attrs, and a reference() returning expected outputs."""

    def run_op(self, op_type, inputs, outputs_spec, attrs=None,
               fetch=None):
        """Build a one-op program and run it; returns dict name->np array."""
        main = Program()
        startup = Program()
        with program_guard(main, startup):
            block = main.global_block()
            in_names = {}
            feed = {}
            for slot, arrs in inputs.items():
                items = arrs if isinstance(arrs, list) else [(slot.lower(), arrs)]
                names = []
                for name, arr in items:
                    arr = np.asarray(arr)
                    block.create_var(
                        name=name, shape=list(arr.shape),
                        dtype=convert_np_dtype_to_dtype_(arr.dtype),
                        stop_gradient=False,
                    )
                    feed[name] = arr
                    names.append(name)
                in_names[slot] = names
            out_names = {}
            for slot, n_outs in outputs_spec.items():
                names = ["%s_out_%d" % (slot.lower(), i) for i in range(n_outs)]
                for n in names:
                    block.create_var(name=n, shape=None, dtype="float32")
                out_names[slot] = names
            block.append_op(type=op_type, inputs=in_names,
                            outputs=out_names, attrs=attrs or {})
            exe = fluid.Executor(fluid.CPUPlace())
            fetch_names = fetch or [n for ns in out_names.values() for n in ns]
            res = exe.run(main, feed=feed, fetch_list=fetch_names)
        return dict(zip(fetch_names, res))

    def check_output(self, op_type, inputs, outputs, attrs=None, atol=1e-5,
                     rtol=1e-5):
        """outputs: dict slot -> expected np array (single-var slots)."""
        spec = {slot: 1 for slot in outputs}
        fetch = ["%s_out_0" % slot.lower() for slot in outputs]
        got = self.run_op(op_type, inputs, spec, attrs, fetch)
        for slot, expected in outputs.items():
            actual = got["%s_out_0" % slot.lower()]
            np.testing.assert_allclose(
                actual, expected, atol=atol, rtol=rtol,
                err_msg="output mismatch for %s.%s" % (op_type, slot),
            )

    def check_grad(self, op_type, inputs, grad_input_name, attrs=None,
                   output_slot="Out", delta=1e-3, atol=1e-2, rtol=1e-2,
                   loss_reduce="mean"):
        """Central finite differences vs programmatic gradient, matching the
        reference's get_numeric_gradient (op_test.py:44)."""
        # programmatic gradient via a tiny program: out = reduce(op(x)); grad
        main = Program()
        startup = Program()
        with program_guard(main, startup):
            block = main.global_block()
            feed = {}
            in_vars = {}
            for slot, arrs in inputs.items():
                items = arrs if isinstance(arrs, list) else [(slot.lower(), arrs)]
                names = []
                for name, arr in items:
                    arr = np.asarray(arr)
                    v = block.create_var(
                        name=name, shape=list(arr.shape),
                        dtype=convert_np_dtype_to_dtype_(arr.dtype),
                        stop_gradient=(arr.dtype.kind in "iub"),
                    )
                    feed[name] = arr
                    names.append(name)
                in_vars[slot] = names
            out = block.create_var(name="op_out", shape=None, dtype="float32")
            block.append_op(
                type=op_type, inputs=in_vars,
                outputs={output_slot: ["op_out"]}, attrs=attrs or {},
            )
            out_v = block.vars["op_out"]
            loss = fluid.layers.mean(out_v)
            fluid.append_backward(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            gname = grad_input_name + "@GRAD"
            (analytic,) = exe.run(main, feed=feed, fetch_list=[gname])

        # numeric gradient of mean(op(x)) wrt the named input: one
        # forward-only program and one executor, compiled once; each
        # evaluation changes only the fed value of the perturbed input
        main2 = Program()
        startup2 = Program()
        feed2 = {}
        with program_guard(main2, startup2):
            block = main2.global_block()
            in_vars2 = {}
            for slot, arrs in inputs.items():
                items = arrs if isinstance(arrs, list) else [
                    (slot.lower(), arrs)
                ]
                names = []
                for name, arr in items:
                    arr = np.asarray(arr)
                    block.create_var(
                        name=name, shape=list(arr.shape),
                        dtype=convert_np_dtype_to_dtype_(arr.dtype),
                    )
                    feed2[name] = arr
                    names.append(name)
                in_vars2[slot] = names
            block.create_var(name="op_out", shape=None, dtype="float32")
            block.append_op(
                type=op_type, inputs=in_vars2,
                outputs={output_slot: ["op_out"]}, attrs=attrs or {},
            )
        exe2 = fluid.Executor(fluid.CPUPlace())
        x_orig = feed2[grad_input_name]

        def f(x_flat):
            feed2[grad_input_name] = x_flat.reshape(x_orig.shape).astype(
                x_orig.dtype)
            (val,) = exe2.run(main2, feed=feed2, fetch_list=["op_out"])
            return float(np.mean(val))

        base = None
        for slot, arrs in inputs.items():
            items = arrs if isinstance(arrs, list) else [(slot.lower(), arrs)]
            for name, arr in items:
                if name == grad_input_name:
                    base = np.asarray(arr, dtype=np.float64)
        assert base is not None
        flat = base.flatten()
        numeric = np.zeros_like(flat)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + delta
            fp = f(flat)
            flat[i] = orig - delta
            fm = f(flat)
            flat[i] = orig
            numeric[i] = (fp - fm) / (2 * delta)
        numeric = numeric.reshape(base.shape)
        np.testing.assert_allclose(
            analytic, numeric, atol=atol, rtol=rtol,
            err_msg="gradient mismatch for %s input %s"
                    % (op_type, grad_input_name),
        )
