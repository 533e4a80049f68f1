"""Machine model (Unity cost model v1 analogue) tests.

Coverage model: the reference's Simulator/MachineModel layer
(lib/runtime/src/simulator.h:161-714) had no unit tests; these follow the
compiler-test pattern instead (hand-built fixtures, canned expectations).
"""

import json

import pytest

from flexflow_tpu.compiler.machine_model import (
    EnhancedTPUMachineModel,
    MachineModelCommModel,
    NetworkedMachineModel,
    SimpleMachineModel,
    _near_square_factorization,
    big_switch_topology,
    machine_model_from_config,
    torus_topology,
)
from flexflow_tpu.pcg.machine_view import MachineSpecification


def spec(nodes=2, chips=8, dcn=25.0, ici=400.0):
    return MachineSpecification(nodes, 1, chips, dcn, ici)


class TestFactorization:
    def test_balanced(self):
        assert _near_square_factorization(8) == (2, 2, 2)
        assert _near_square_factorization(16) == (2, 2, 4)
        assert _near_square_factorization(1) == (1,)
        prod = 1
        for d in _near_square_factorization(64):
            prod *= d
        assert prod == 64


class TestSimpleMachineModel:
    def test_paths(self):
        m = SimpleMachineModel(spec())
        assert m.get_comm_path(0, 0) == []
        intra = m.get_comm_path(0, 3)
        assert len(intra) == 1 and intra[0].kind == "ici"
        inter = m.get_comm_path(0, 9)  # dev 9 is node 1
        assert len(inter) == 1 and inter[0].kind == "dcn"

    def test_xfer_cost_scales_with_bytes(self):
        m = SimpleMachineModel(spec())
        small = m.estimate_xfer_cost(1e6, [(0, 1)])
        large = m.estimate_xfer_cost(1e8, [(0, 1)])
        assert large > small > 0

    def test_congestion_on_shared_link(self):
        m = SimpleMachineModel(spec())
        # two transfers over the same node pair share the DCN link
        one = m.estimate_xfer_cost(1e8, [(0, 8)])
        two = m.estimate_xfer_cost(1e8, [(0, 8), (1, 9)])
        assert two > one


class TestEnhancedModel:
    def test_torus_route_hops(self):
        m = EnhancedTPUMachineModel(spec(nodes=1, chips=8), ici_dims=(2, 4))
        # (0,0) -> (1,2): 1 hop on axis 0 + 2 hops on axis 1
        path = m.get_comm_path(m.chip_id(0, (0, 0)), m.chip_id(0, (1, 2)))
        assert len(path) == 3
        assert all(l.kind == "ici" for l in path)

    def test_wraparound_takes_short_direction(self):
        m = EnhancedTPUMachineModel(spec(nodes=1, chips=8), ici_dims=(2, 4))
        # axis-1 distance 3 forward == 1 backward via wraparound
        path = m.get_comm_path(m.chip_id(0, (0, 0)), m.chip_id(0, (0, 3)))
        assert len(path) == 1

    def test_cross_slice_path_has_dcn(self):
        m = EnhancedTPUMachineModel(spec(nodes=2, chips=8), ici_dims=(2, 4))
        path = m.get_comm_path(0, 15)
        kinds = [l.kind for l in path]
        assert "dcn" in kinds and "nic_out" in kinds and "nic_in" in kinds

    def test_per_link_congestion(self):
        m = EnhancedTPUMachineModel(spec(nodes=1, chips=4), ici_dims=(4,))
        # two transfers sharing the 0->1 link vs two disjoint transfers
        shared = m.estimate_xfer_cost(1e8, [(0, 1), (0, 1)])
        disjoint = m.estimate_xfer_cost(1e8, [(0, 1), (2, 3)])
        assert shared > disjoint


class TestNetworkedModel:
    def test_bfs_route_on_ring(self):
        links = torus_topology((4,), 100.0)
        m = NetworkedMachineModel(4, links)
        assert len(m.get_comm_path(0, 1)) == 1
        assert len(m.get_comm_path(0, 2)) == 2
        assert len(m.get_comm_path(0, 3)) == 1  # wraparound

    def test_big_switch(self):
        m = NetworkedMachineModel(4, big_switch_topology(4, 50.0))
        assert len(m.get_comm_path(0, 3)) == 1

    def test_unreachable(self):
        m = NetworkedMachineModel(4, {})
        assert m.get_comm_path(0, 3) == []


class TestConfigSelection:
    def test_versions(self, tmp_path):
        s = spec()
        assert isinstance(machine_model_from_config(s, 0), SimpleMachineModel)
        assert isinstance(
            machine_model_from_config(s, 1), EnhancedTPUMachineModel)
        assert isinstance(
            machine_model_from_config(s, 2), NetworkedMachineModel)

    def test_enhanced_from_file(self, tmp_path):
        f = tmp_path / "mm.json"
        f.write_text(json.dumps({
            "ici_dims": [2, 4], "ici_link_gbps": 123.0,
            "nic_ports_per_node": 2,
        }))
        m = machine_model_from_config(spec(), 1, str(f))
        assert m.ici_dims == (2, 4)
        assert m.ici_link_gbps == 123.0
        assert m.nic_ports == 2

    def test_bad_version(self):
        with pytest.raises(ValueError):
            machine_model_from_config(spec(), 9)


class TestMovementAdapter:
    def test_multi_view_movement(self):
        """Movements with several src/dst views (branching consumers) must
        not crash and must cost more than a single-destination move."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            SingleTensorMovement,
            TensorSetMovement,
        )
        from flexflow_tpu.op_attrs import (
            ParallelTensorDims,
            ParallelTensorShape,
            ShardParallelDim,
            TensorShape,
        )
        from flexflow_tpu.pcg.machine_view import (
            DeviceType,
            MachineSpaceCoordinate,
            MachineView,
            MachineViewDimension,
            ProjectionType,
        )

        s = spec(nodes=1, chips=8)
        shape = ParallelTensorShape(
            ParallelTensorDims(
                (ShardParallelDim(64, 2), ShardParallelDim(32, 1)), 1, 1
            )
        )

        def view(start_dev):
            return MachineView(
                MachineSpaceCoordinate(0, start_dev, DeviceType.TPU),
                (
                    MachineViewDimension(1, ProjectionType.INTRA_NODE),
                    MachineViewDimension(1, ProjectionType.INTRA_NODE),
                ),
            )

        comm = MachineModelCommModel(
            s, EnhancedTPUMachineModel(s, ici_dims=(2, 4)))
        one = comm.movement_cost_ms(TensorSetMovement((
            SingleTensorMovement(
                shape, frozenset({view(0)}), frozenset({view(2)})),
        )))
        # dsts 1 and 2 both route through the 0->1 ICI link (dimension-
        # ordered), so the shared link's load doubles
        two = comm.movement_cost_ms(TensorSetMovement((
            SingleTensorMovement(
                shape, frozenset({view(0)}),
                frozenset({view(1), view(2)})),
        )))
        assert two > one > 0

    def test_dp_runs_with_topology_comm_model(self):
        """The machine-mapping DP accepts the topology-aware comm model."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler import (
            MachineMappingCache,
            MachineMappingContext,
            get_machine_mapping_problem_tree,
            get_optimal_machine_mapping,
        )
        from flexflow_tpu.pcg import ComputationGraphBuilder
        from flexflow_tpu.pcg.parallel_computation_graph import (
            pcg_from_computation_graph,
        )

        s = spec(nodes=1, chips=4)
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16], name="x")
        h = b.dense(x, 32, use_bias=False)
        h = b.relu(h)
        h = b.dense(h, 8, use_bias=False)
        pcg = pcg_from_computation_graph(b.graph)
        comm = MachineModelCommModel(
            s, EnhancedTPUMachineModel(s, ici_dims=(4,)))
        ctx = MachineMappingContext(
            AnalyticTPUCostEstimator(s, comm_model=comm),
            make_default_allowed_machine_views(),
        )
        tree, _ = get_machine_mapping_problem_tree(pcg)
        result = get_optimal_machine_mapping(
            MachineMappingCache(), ctx, tree, s)
        assert result.runtime < float("inf")


class TestPerAxisLinkPricing:
    """Round-4 cost-model refinements: a collective rides the link of the
    op's OWN axis, and a boundary reshard rides the DCN only when the
    node-level placement changes (cost_estimator._parallel_op_crosses_nodes
    and the labeled inter signatures in movement_cost_ms)."""

    def _view(self, projs):
        from flexflow_tpu.pcg.machine_view import (
            DeviceType,
            MachineSpaceCoordinate,
            MachineView,
            MachineViewDimension,
        )

        return MachineView(
            MachineSpaceCoordinate(0, 0, DeviceType.TPU),
            tuple(MachineViewDimension(1, p) for p in projs),
        )

    def _spec(self):
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        return MachineSpecification(2, 1, 4, 25.0, 400.0)

    def _pts(self, degrees, sum_degree=1, copy=1):
        from flexflow_tpu.op_attrs.datatype import DataType
        from flexflow_tpu.op_attrs.parallel_tensor_shape import (
            ParallelTensorDims,
            ParallelTensorShape,
            ShardParallelDim,
        )

        return ParallelTensorShape(
            ParallelTensorDims(
                tuple(ShardParallelDim(64, d) for d in degrees),
                sum_degree,
                copy,
            ),
            DataType.FLOAT,
        )

    def test_tp_reduction_inside_dp_inter_plan_rides_ici(self):
        """A Reduction draining a tp=4 sum inside a dp2-across-nodes plan:
        its view carries the dp INTER dim, but the psum axes fit beside it
        on ICI — must NOT be priced at DCN."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            _parallel_op_crosses_nodes,
        )
        from flexflow_tpu.op_attrs.ops import ReductionAttrs
        from flexflow_tpu.pcg.machine_view import ProjectionType as PT

        # input: [b/2, e] with sum_degree 4; output task space = (2,)
        pts = self._pts([2, 1], sum_degree=4)
        view = self._view([PT.INTER_NODE])
        assert not _parallel_op_crosses_nodes(
            ReductionAttrs(4), [pts], view, self._spec()
        )

    def test_degree8_reduction_cannot_fit_ici(self):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            _parallel_op_crosses_nodes,
        )
        from flexflow_tpu.op_attrs.ops import ReductionAttrs
        from flexflow_tpu.pcg.machine_view import ProjectionType as PT

        pts = self._pts([1, 1], sum_degree=8)
        view = self._view([])  # degree-8 sum drained: output task trivial
        # view dims (0) == entries (0): removed axis 8 > 4 per node -> DCN
        assert _parallel_op_crosses_nodes(
            ReductionAttrs(8), [pts], view, self._spec()
        )

    def test_replicate_inter_projection_rides_dcn(self):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            _parallel_op_crosses_nodes,
        )
        from flexflow_tpu.op_attrs.ops import ReplicateAttrs
        from flexflow_tpu.pcg.machine_view import ProjectionType as PT

        pts = self._pts([1, 1])
        view = self._view([PT.INTER_NODE])  # copy degree projected INTER
        assert _parallel_op_crosses_nodes(
            ReplicateAttrs(2), [pts], view, self._spec()
        )
        view2 = self._view([PT.INTRA_NODE])
        assert not _parallel_op_crosses_nodes(
            ReplicateAttrs(2), [pts], view2, self._spec()
        )

    def test_movement_same_inter_signature_rides_ici(self):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            BandwidthCommModel,
            SingleTensorMovement,
            TensorSetMovement,
        )
        from flexflow_tpu.pcg.machine_view import ProjectionType as PT

        model = BandwidthCommModel(self._spec())
        pts = self._pts([2, 4])
        same = self._view([PT.INTER_NODE, PT.INTRA_NODE])
        m_ici = TensorSetMovement((
            SingleTensorMovement(
                pts,
                frozenset({same}),
                frozenset({self._view([PT.INTER_NODE, PT.INTRA_NODE])}),
            ),
        ))
        # identical views -> zero; build a dst differing only INTRA
        cost_same_sig = model.movement_cost_ms(m_ici)
        # dst where the INTER structure moves to the other dim -> DCN
        m_dcn = TensorSetMovement((
            SingleTensorMovement(
                pts,
                frozenset({same}),
                frozenset({self._view([PT.INTRA_NODE, PT.INTER_NODE])}),
            ),
        ))
        cost_diff_sig = model.movement_cost_ms(m_dcn)
        assert cost_diff_sig > cost_same_sig

    def test_movement_same_arity_different_dim_rides_dcn(self):
        """Round-5 advisor fix: a batch-INTER producer feeding a consumer
        whose equal-arity view shards a DIFFERENT tensor dim INTER crosses
        the DCN; same-dim consumers (Megatron within-node alternation) stay
        on ICI. Dim identity comes from dst_view_shapes."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            BandwidthCommModel,
            SingleTensorMovement,
            TensorSetMovement,
        )
        from flexflow_tpu.pcg.machine_view import ProjectionType as PT

        model = BandwidthCommModel(self._spec())
        src_pts = self._pts([2, 1])  # batch-sharded producer output
        view = self._view([PT.INTER_NODE])
        # consumer output feature-sharded (dim 1) with the same arity-1 view
        feat_pts = self._pts([1, 2])
        m_feat = TensorSetMovement((
            SingleTensorMovement(
                src_pts,
                frozenset({view}),
                frozenset({self._view([PT.INTER_NODE])}),
                frozenset({(self._view([PT.INTER_NODE]), feat_pts)}),
            ),
        ))
        # consumer output batch-sharded (dim 0): same tensor dim -> ICI
        m_batch = TensorSetMovement((
            SingleTensorMovement(
                src_pts,
                frozenset({view}),
                frozenset({self._view([PT.INTER_NODE])}),
                frozenset({(self._view([PT.INTER_NODE]), self._pts([2, 1]))}),
            ),
        ))
        assert model.movement_cost_ms(m_feat) > model.movement_cost_ms(m_batch)


def test_machine_constants_are_keyed_by_device_kind_and_unknown_raises():
    """One sourced table; a device that is not in it is an error, never
    another chip's peaks."""
    from flexflow_tpu.compiler.machine_constants import machine_constants

    v5e = machine_constants("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_gbps) == (197e12, 819.0)
    assert "TPU v5e" in v5e.source
    assert machine_constants() is machine_constants("cpu")  # the test mesh
    with pytest.raises(ValueError, match="TPU v9"):
        machine_constants("TPU v9")
