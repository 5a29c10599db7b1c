"""Container layers (reference: python/paddle/fluid/dygraph/container.py:
Sequential, ParameterList, LayerList)."""

from __future__ import annotations

from .layers import Layer, Parameter


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) \
                and not isinstance(layers[0], Layer):
            layers = layers[0]
        if layers and isinstance(layers[0], tuple) \
                and not isinstance(layers[0], Layer):
            for name, layer in layers:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers)
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, input):
        for layer in self._sub_layers.values():
            input = layer(input)
        return input


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, layer in enumerate(sublayers):
                self.add_sublayer(str(i), layer)

    # a LayerList is iterated, never called: its children carry its
    # name in theirs (`layers/3`), so a scope path reads as the
    # attribute path does
    def _set_scope_name(self, name):
        super()._set_scope_name(name)
        for key, layer in self._sub_layers.items():
            layer._set_scope_name(f"{name}/{key}")

    def add_sublayer(self, name, sublayer):
        super().add_sublayer(name, sublayer)
        if self._scope_name:
            sublayer._set_scope_name(f"{self._scope_name}/{name}")
        return sublayer

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers)
        return self._sub_layers[keys[idx]]

    def __setitem__(self, idx, layer):
        keys = list(self._sub_layers)
        self.add_sublayer(keys[idx], layer)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, layers):
        for layer in layers:
            self.append(layer)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        keys = list(self._parameters)
        return self._parameters[keys[idx]]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self
