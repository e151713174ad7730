"""Plain float32 reference of the ResNet family and the H-SADMM round,
independent of the system under test."""
