# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: gome_tpu/api/order.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x18gome_tpu/api/order.proto\x12\x0cgome_tpu.api\"\xb3\x01\n\x0cOrderRequest\x12\x0c\n\x04uuid\x18\x01 \x01(\t\x12\x0b\n\x03oid\x18\x02 \x01(\t\x12\x0e\n\x06symbol\x18\x03 \x01(\t\x12\x32\n\x0btransaction\x18\x04 \x01(\x0e\x32\x1d.gome_tpu.api.TransactionType\x12\r\n\x05price\x18\x05 \x01(\x01\x12\x0e\n\x06volume\x18\x06 \x01(\x01\x12%\n\x04kind\x18\x07 \x01(\x0e\x32\x17.gome_tpu.api.OrderKind\".\n\rOrderResponse\x12\x0c\n\x04\x63ode\x18\x01 \x01(\x05\x12\x0f\n\x07message\x18\x02 \x01(\t\"~\n\nMatchEvent\x12)\n\x04node\x18\x01 \x01(\x0b\x32\x1b.gome_tpu.api.OrderSnapshot\x12/\n\nmatch_node\x18\x02 \x01(\x0b\x32\x1b.gome_tpu.api.OrderSnapshot\x12\x14\n\x0cmatch_volume\x18\x03 \x01(\x01\"\x8d\x01\n\rOrderSnapshot\x12\x0c\n\x04uuid\x18\x01 \x01(\t\x12\x0b\n\x03oid\x18\x02 \x01(\t\x12\x0e\n\x06symbol\x18\x03 \x01(\t\x12\x32\n\x0btransaction\x18\x04 \x01(\x0e\x32\x1d.gome_tpu.api.TransactionType\x12\r\n\x05price\x18\x05 \x01(\x01\x12\x0e\n\x06volume\x18\x06 \x01(\x01\"\x12\n\x10SubscribeRequest\"O\n\x11OrderBatchRequest\x12*\n\x06orders\x18\x01 \x03(\x0b\x32\x1a.gome_tpu.api.OrderRequest\x12\x0e\n\x06\x63\x61ncel\x18\x02 \x03(\x08\"\x89\x01\n\x12OrderBatchResponse\x12\x0c\n\x04\x63ode\x18\x01 \x01(\x05\x12\x0f\n\x07message\x18\x02 \x01(\t\x12\x10\n\x08\x61\x63\x63\x65pted\x18\x03 \x01(\x05\x12\x14\n\x0creject_index\x18\x04 \x03(\x05\x12,\n\x07rejects\x18\x05 \x03(\x0b\x32\x1b.gome_tpu.api.OrderResponse*$\n\x0fTransactionType\x12\x07\n\x03\x42UY\x10\x00\x12\x08\n\x04SALE\x10\x01*C\n\tOrderKind\x12\t\n\x05LIMIT\x10\x00\x12\n\n\x06MARKET\x10\x01\x12\x07\n\x03IOC\x10\x03\x12\x07\n\x03\x46OK\x10\x04\x12\r\n\tPOST_ONLY\x10\x06\x32\x91\x03\n\x05Order\x12\x44\n\x07\x44oOrder\x12\x1a.gome_tpu.api.OrderRequest\x1a\x1b.gome_tpu.api.OrderResponse\"\x00\x12H\n\x0b\x44\x65leteOrder\x12\x1a.gome_tpu.api.OrderRequest\x1a\x1b.gome_tpu.api.OrderResponse\"\x00\x12P\n\x10SubscribeMatches\x12\x1e.gome_tpu.api.SubscribeRequest\x1a\x18.gome_tpu.api.MatchEvent\"\x00\x30\x01\x12S\n\x0c\x44oOrderBatch\x12\x1f.gome_tpu.api.OrderBatchRequest\x1a .gome_tpu.api.OrderBatchResponse\"\x00\x12Q\n\rDoOrderStream\x12\x1a.gome_tpu.api.OrderRequest\x1a .gome_tpu.api.OrderBatchResponse\"\x00(\x01\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'gome_tpu.api.order_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TRANSACTIONTYPE._serialized_start=785
  _TRANSACTIONTYPE._serialized_end=821
  _ORDERKIND._serialized_start=823
  _ORDERKIND._serialized_end=890
  _ORDERREQUEST._serialized_start=43
  _ORDERREQUEST._serialized_end=222
  _ORDERRESPONSE._serialized_start=224
  _ORDERRESPONSE._serialized_end=270
  _MATCHEVENT._serialized_start=272
  _MATCHEVENT._serialized_end=398
  _ORDERSNAPSHOT._serialized_start=401
  _ORDERSNAPSHOT._serialized_end=542
  _SUBSCRIBEREQUEST._serialized_start=544
  _SUBSCRIBEREQUEST._serialized_end=562
  _ORDERBATCHREQUEST._serialized_start=564
  _ORDERBATCHREQUEST._serialized_end=643
  _ORDERBATCHRESPONSE._serialized_start=646
  _ORDERBATCHRESPONSE._serialized_end=783
  _ORDER._serialized_start=893
  _ORDER._serialized_end=1294
# @@protoc_insertion_point(module_scope)
